"""Serving engine: continuous batching over a paged KV pool, with the wave
scheduler kept as the reference path.

Two schedulers share one engine:

* ``continuous`` (default when the executor implements the paged protocol)
  — a fixed decode batch of ``max_batch`` *slots* over a shared
  :class:`~repro.serving.kvpool.PagedKVPool`.  Requests are admitted from
  the queue the moment a slot frees (respecting pool capacity), prefill
  writes prompt KV straight into pool pages, every decode step advances all
  live slots at their own depths, and finished requests retire per-slot
  (EOS / max-len), returning their pages for reuse.  No slot idles while
  work is queued — the fix for wave-at-a-time decode, where a finished
  request left its batch slot dead until the whole wave drained.
* ``wave`` — batch same-length prompts, prefill together, decode in
  lockstep.  Kept both as the fallback for executors without the paged
  protocol and as the correctness oracle: for greedy sampling the two
  schedulers produce identical tokens, which tests pin on both executors.

Two continuous-scheduler extensions target prompt-heavy edge traffic:

* **Shared-prefix KV cache** (``prefix_cache=True``): admission runs the
  radix-tree lookup of :class:`~repro.serving.prefix_cache.PrefixCache`
  over the prompt, attaches the hit's already-filled pages to the slot by
  *refcount bump* (``PagedKVPool.admit(shared_pages=...)`` — no new
  allocation, pages free only at refcount zero), and prefills **only the
  uncached suffix** (the executor's ``prefill_chunk`` starts at the cached
  offset and attends back to the shared pages).  After prefill the
  request's own full prompt pages are inserted into the tree for later
  requests; retirement decrements refcounts, and under memory pressure the
  tree evicts idle LRU pages.  Decode needs no changes: reads are
  block-table gathers, each slot writes only its own (never shared) tail
  page.
* **Chunked prefill** (``prefill_chunk=N``): instead of stalling every
  live decode slot for a whole long-prompt prefill, admission queues a
  prefill *task* and the main loop interleaves one N-token (grain-rounded)
  chunk per iteration with the decode step, bounding time-to-first-token
  jitter for already-decoding requests.  Chunks attend back to the pages
  earlier chunks wrote, so the math equals the one-shot prefill.

The engine is model-agnostic: it drives an *executor* exposing
``make_cache`` / ``prefill`` / ``decode`` (wave) and, optionally, the paged
protocol ``supports_paged`` / ``make_pool`` / ``prefill_paged`` /
``decode_paged`` (plus ``prefill_chunk`` for the prefix/chunked paths) and
the ``prompt_pad_multiple`` padding policy (1 for the single-device
``TransformerExecutor``; the mesh size for
``serving.galaxy.GalaxyHMPExecutor``, whose SP prefill needs sequence
multiples).  All shape-dependent functions are jitted once per shape bucket
and reused.

Observability (``repro.obs``): every engine owns a
:class:`~repro.obs.metrics.MetricsRegistry` (``engine.metrics``) and the
old hand-rolled stats dict survives as a read/write *facade* over it
(``engine.stats["decode_steps"]`` keeps working; ``engine.reset_stats()``
zeroes the per-run scope while the registry's lifetime scope keeps
accumulating — the fix for counters silently persisting across ``run()``
calls on a reused engine).  Two opt-in hooks add the expensive signals:

* ``tracer=`` (:class:`~repro.obs.trace.Tracer`) records spans for the
  whole request lifecycle — submit → queued → admitted (prefix lookup) →
  each prefill chunk → each decode step / speculative round (rollback) →
  retire — on one track per request plus an engine-loop track, exportable
  as Chrome trace-event JSON.  The engine-loop spans (``engine.step`` and
  its children, :func:`~repro.obs.trace.span`) are also ``jax.profiler``
  annotations, always entered, so a profiler capture puts them on the
  device trace's clock.  Tracing never synchronizes the device; a run
  without a tracer executes zero Tracer instructions and a bounded number
  of annotations per step, none per token (gated structurally in
  ``tests/test_obs.py``).
* ``drift=`` (:class:`~repro.obs.drift.DriftMonitor`) prices each executed
  step with the planner's simulator and histograms measured/simulated —
  the live costmodel-drift signal.  Drift is a diagnostics mode: it adds
  one ``block_until_ready`` per mid-prompt prefill chunk so chunk ratios
  are wall time (decode steps and verify chunks already sync at sampling).

TTFT / inter-token-latency histograms (``ttft_s`` / ``itl_s``) fill from
the same ``record_times`` stamps as before, at retirement — enable
``record_times=True`` to populate them.  Neither hook perturbs sampling:
greedy tokens are bitwise identical with telemetry on or off.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import defaultdict, deque
from collections.abc import MutableMapping
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.sharding import Rules, axis_rules
from repro.models.transformer import apply_model
from repro.obs.drift import DriftMonitor
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import RequestTracks, Tracer, span
from repro.serving.kvcache import cache_page_size, make_cache, map_cache_leaves
from repro.serving.kvpool import PagedKVPool
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampler import SamplerConfig, sample, sample_positions
from repro.serving.spec import SpeculativeDecoder, run_spec_round


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # perf_counter stamp per emitted token (filled when the engine runs with
    # record_times=True; the microbench derives per-token latency from it)
    token_times: List[float] = dataclasses.field(default_factory=list)
    # perf_counter stamp at submit() (record_times=True); TTFT per request
    # is token_times[0] - submit_time (see benchmarks/run.py:ttft_percentiles)
    submit_time: Optional[float] = None


def _roundup(x: int, m: int) -> int:
    return -(-x // m) * m


class TransformerExecutor:
    """Default executor: the GSPMD model zoo (models/transformer.py)."""

    def __init__(self, params, cfg: ModelConfig, rules: Optional[Rules] = None):
        self.params = params
        self.cfg = cfg
        self.rules = rules
        self._prefill_fns: Dict = {}
        self._decode_fn = None
        self._decode_paged_fn = None

    # --- padding policy ------------------------------------------------------
    @property
    def prompt_pad_multiple(self) -> int:
        """Prompts need no length padding on a single GSPMD program."""
        return 1

    # --- wave protocol -------------------------------------------------------
    def make_cache(self, batch: int, max_len: int):
        return make_cache(self.cfg, batch, max_len, rules=self.rules)

    def prefill(self, tokens, cache, lengths=None):
        """Prefill a batch of prompts.  ``lengths`` (B,) gathers each row's
        last *real* logit when prompts were right-padded to a shared length;
        None keeps the single-length fast path (logits of the last column)."""
        b, s = tokens.shape
        key = (b, s, lengths is not None)
        if key not in self._prefill_fns:
            cfg, rules = self.cfg, self.rules

            def prefill(params, tokens, cache, lengths=None):
                with axis_rules(rules):
                    logits, cache, _ = apply_model(
                        params, cfg, tokens=tokens, mode="prefill", cache=cache
                    )
                if lengths is None:
                    return logits[:, -1], cache
                return logits[jnp.arange(b), lengths - 1], cache

            self._prefill_fns[key] = jax.jit(prefill)
        if lengths is None:
            return self._prefill_fns[key](self.params, tokens, cache)
        return self._prefill_fns[key](self.params, tokens, cache, lengths)

    def decode(self, tokens, cache, index):
        if self._decode_fn is None:
            cfg, rules = self.cfg, self.rules

            def decode(params, tokens, cache, index):
                with axis_rules(rules):
                    logits, cache, _ = apply_model(
                        params, cfg, tokens=tokens, mode="decode",
                        cache=cache, cache_index=index,
                    )
                return logits[:, -1], cache

            self._decode_fn = jax.jit(decode)
        return self._decode_fn(self.params, tokens, cache, index)

    # --- paged protocol ------------------------------------------------------
    @property
    def supports_paged(self) -> bool:
        """Paged serving covers full-causal attention stacks; recurrent and
        sliding-window caches are not position-addressable pages."""
        cfg = self.cfg
        kinds = tuple(cfg.block_pattern) + tuple(cfg.tail_pattern)
        return all(k == "attn" for k in kinds) and cfg.window == 0

    def make_pool(self, num_pages: int, page_size: int):
        """Pool storage: the model-zoo cache pytree with (batch, seq) read as
        (page, in-page slot) — every leaf is (groups?, P, page_size, kv, hd)."""
        return make_cache(self.cfg, num_pages, page_size, rules=self.rules)

    def prefill_paged(self, tokens, pool, block_row, length: int):
        """Prefill one request (batch 1) and scatter its KV into pool pages.

        tokens: (1, S_pad); length: real prompt length (logits are taken at
        ``length - 1``); block_row: (W,) physical pages of this request.
        """
        b, s = tokens.shape
        if b != 1:
            raise ValueError("paged prefill is per-request: batch must be 1")
        key = ("paged", s)
        if key not in self._prefill_fns:
            cfg, rules = self.cfg, self.rules

            # length stays a traced scalar so every prompt sharing this
            # padded shape reuses one compiled program
            def prefill(params, tokens, pool, block_row, length):
                page_size = cache_page_size(pool)
                with axis_rules(rules):
                    dense = make_cache(cfg, 1, s)
                    logits, dense, _ = apply_model(
                        params, cfg, tokens=tokens, mode="prefill", cache=dense
                    )
                pos = jnp.arange(s)
                phys = block_row[pos // page_size]
                within = pos % page_size

                def scatter(leaf, new, grouped):
                    if grouped:
                        return leaf.at[:, phys, within].set(new[:, 0])
                    return leaf.at[phys, within].set(new[0])

                pool = map_cache_leaves(pool, dense, scatter)
                return logits[:, length - 1], pool

            # donate the pool so XLA scatters into the pages in place
            # instead of copying the whole pool every call
            self._prefill_fns[key] = jax.jit(prefill, donate_argnums=(2,))
        return self._prefill_fns[key](
            self.params, tokens, pool, block_row, jnp.asarray(length, jnp.int32)
        )

    def prefill_chunk(self, tokens, pool, block_row, *, offset, length):
        """One chunked-prefill step (batch 1): gather the slot's pages into
        a dense per-request cache view, run the chunk at absolute positions
        [offset, offset + S) attending back to every already-written
        position (earlier chunks and shared prefix pages), and scatter the
        chunk's KV into its pages.  Returns ``(logits, pool)`` where
        ``logits`` holds *every* chunk row, (1, S, V): row ``j`` predicts
        position ``offset + j + 1``.  Chunked prompt prefill reads only the
        last real prompt token's row; speculative verification
        (``serving/spec.py``) compares all rows against the draft.
        """
        b, s = tokens.shape
        if b != 1:
            raise ValueError("paged prefill is per-request: batch must be 1")
        key = ("chunk", s)
        if key not in self._prefill_fns:
            cfg, rules = self.cfg, self.rules

            # offset/length stay traced scalars: one compiled program per
            # chunk shape, shared by every offset it runs at
            def prefill(params, tokens, pool, block_row, offset, length):
                page_size = cache_page_size(pool)
                w = block_row.shape[0]

                def gather(leaf, _, grouped):
                    if grouped:
                        g = leaf[:, block_row]  # (G, W, page, kv, hd)
                        return g.reshape(g.shape[0], 1, w * page_size,
                                         *g.shape[3:])
                    g = leaf[block_row]
                    return g.reshape(1, w * page_size, *g.shape[2:])

                dense = map_cache_leaves(pool, pool, gather)
                with axis_rules(rules):
                    logits, dense, _ = apply_model(
                        params, cfg, tokens=tokens, mode="prefill",
                        cache=dense, cache_index=offset,
                    )
                pos = offset + jnp.arange(s)
                phys = block_row[pos // page_size]
                within = pos % page_size

                def scatter(leaf, new, grouped):
                    if grouped:
                        return leaf.at[:, phys, within].set(new[:, 0, pos])
                    return leaf.at[phys, within].set(new[0, pos])

                pool = map_cache_leaves(pool, dense, scatter)
                return logits, pool

            self._prefill_fns[key] = jax.jit(prefill, donate_argnums=(2,))
        return self._prefill_fns[key](
            self.params, tokens, pool, block_row,
            jnp.asarray(offset, jnp.int32), jnp.asarray(length, jnp.int32),
        )

    def decode_paged(self, tokens, pool, block_table, positions):
        """One continuous-batching step: gather each slot's pages into a
        dense per-slot view, run the single-token model at per-slot depths,
        scatter the new KV entry back into its page."""
        if self._decode_paged_fn is None:
            cfg, rules = self.cfg, self.rules

            def decode(params, tokens, pool, bt, positions):
                page_size = cache_page_size(pool)
                slots, w = bt.shape
                rows = jnp.arange(slots)

                def gather(leaf, _, grouped):
                    if grouped:
                        g = leaf[:, bt]  # (G, S, W, page, kv, hd)
                        return g.reshape(*g.shape[:2], w * page_size, *g.shape[4:])
                    g = leaf[bt]
                    return g.reshape(slots, w * page_size, *g.shape[3:])

                dense = map_cache_leaves(pool, pool, gather)
                with axis_rules(rules):
                    logits, dense, _ = apply_model(
                        params, cfg, tokens=tokens, mode="decode",
                        cache=dense, cache_index=positions,
                    )
                phys = bt[rows, positions // page_size]
                within = positions % page_size

                def scatter(leaf, new, grouped):
                    if grouped:
                        return leaf.at[:, phys, within].set(new[:, rows, positions])
                    return leaf.at[phys, within].set(new[rows, positions])

                pool = map_cache_leaves(pool, dense, scatter)
                return logits[:, -1], pool

            self._decode_paged_fn = jax.jit(decode, donate_argnums=(2,))
        return self._decode_paged_fn(
            self.params, tokens, pool, block_table, positions
        )


@dataclasses.dataclass
class _Slot:
    """Per-slot decode state for the continuous scheduler."""
    req: Request
    last_token: int
    next_index: int   # absolute position the next decode step writes
    limit: int        # min(max_new_tokens, max_len - prompt_len)


@dataclasses.dataclass
class _PrefillTask:
    """An admitted request whose prompt is (still) being prefilled.

    The pool pages are already reserved (and prefix-hit pages attached);
    ``next_off`` is the first absolute position not yet written — it starts
    at the cached prefix length and advances one chunk per step."""
    req: Request
    slot: int
    tokens: np.ndarray  # (1, s_pad) bucket-padded prompt
    s: int              # real prompt length
    s_pad: int
    limit: int
    next_off: int


class EngineStats(MutableMapping):
    """The engine's historical stats dict, as a facade over the registry.

    Every key the flat dict used to hold reads (and, for counters and the
    shared-pages peak, writes) straight through to the
    :class:`~repro.obs.metrics.MetricsRegistry`, so existing callers —
    ``engine.stats["decode_steps"]``, ``stats["prefill_tokens"] += n`` —
    see identical values while the registry stays the single source of
    truth (snapshots, Prometheus export, run-vs-lifetime scoping).

    Derived keys are computed on read: ``spec_acceptance`` from the
    accepted/proposed counters, ``spec_accept_counts`` as the value-count
    view of the ``spec_accepted_per_round`` histogram.
    """

    _COUNTERS = ("prefill_tokens", "decode_steps", "requests",
                 "decode_tokens", "prefill_chunks", "prefix_hits",
                 "cached_prefix_tokens", "spec_steps", "spec_proposed",
                 "spec_accepted")
    _KEYS = _COUNTERS + ("peak_shared_pages", "spec_acceptance",
                         "spec_accept_counts")

    def __init__(self, metrics: MetricsRegistry):
        self._m = metrics
        for k in self._COUNTERS:
            metrics.counter(k)
        metrics.gauge("peak_shared_pages")
        metrics.histogram("spec_accepted_per_round",
                          "draft tokens accepted per speculative round")

    def __getitem__(self, key):
        if key in self._COUNTERS:
            return self._m.counter(key).value
        if key == "peak_shared_pages":
            return int(self._m.gauge(key).value)
        if key == "spec_acceptance":
            proposed = self._m.counter("spec_proposed").value
            return (self._m.counter("spec_accepted").value / proposed
                    if proposed else 0.0)
        if key == "spec_accept_counts":
            return {int(v): n for v, n in sorted(
                self._m.histogram("spec_accepted_per_round")
                .value_counts().items())}
        raise KeyError(key)

    def __setitem__(self, key, value):
        if key in self._COUNTERS:
            self._m.counter(key).set_run(value)
        elif key == "peak_shared_pages":
            self._m.gauge(key).set(int(value))
        else:
            raise TypeError(
                f"stats[{key!r}] is derived from the metrics registry and "
                f"cannot be assigned"
            )

    def __delitem__(self, key):
        raise TypeError("engine stats keys are fixed")

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def __repr__(self):
        return repr(dict(self))

    def __eq__(self, other):
        if isinstance(other, (dict, MutableMapping)):
            return dict(self) == dict(other)
        return NotImplemented


class ServingEngine:
    def __init__(
        self,
        params=None,
        cfg: Optional[ModelConfig] = None,
        *,
        executor=None,
        max_batch: int = 8,
        max_len: int = 512,
        sampler: SamplerConfig = SamplerConfig(),
        rules: Optional[Rules] = None,
        rng_seed: int = 0,
        scheduler: str = "auto",
        page_size: int = 16,
        num_pages: Optional[int] = None,
        record_times: bool = False,
        prefix_cache: bool = False,
        prefill_chunk: Optional[int] = None,
        draft_executor=None,
        spec_k: Optional[int] = None,
        tracer: Optional[Tracer] = None,
        drift: Optional[DriftMonitor] = None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        if executor is None:
            if params is None or cfg is None:
                raise ValueError("pass either (params, cfg) or an executor")
            executor = TransformerExecutor(params, cfg, rules)
        elif params is not None or cfg is not None or rules is not None:
            raise ValueError(
                "params/cfg/rules belong to the executor; pass one or the other"
            )
        if scheduler not in ("auto", "continuous", "wave"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1 token")
        if (prefix_cache or prefill_chunk) and not hasattr(
                executor, "prefill_chunk"):
            raise ValueError(
                "prefix caching / chunked prefill need an executor with "
                "the prefill_chunk protocol"
            )
        if (draft_executor is None) != (spec_k is None):
            raise ValueError(
                "speculative decoding needs both draft_executor and spec_k"
            )
        if spec_k is not None:
            if spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if scheduler == "wave":
                raise ValueError(
                    "speculative decoding requires the continuous scheduler "
                    "(the wave path has no paged pool to verify against)"
                )
            if sampler.temperature != 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: the verify chunk's "
                    "per-row argmax is the sequential token only at "
                    "temperature=0"
                )
            if not hasattr(executor, "prefill_chunk"):
                raise ValueError(
                    "speculative verification needs the target executor's "
                    "prefill_chunk protocol"
                )
            if not getattr(draft_executor, "supports_paged", False):
                raise ValueError(
                    "draft executor must implement the paged protocol"
                )
        self.executor = executor
        self.max_batch = max_batch
        self.max_len = max_len
        self.sampler = sampler
        self.rng = jax.random.PRNGKey(rng_seed)
        self.scheduler = scheduler
        self.page_size = page_size
        self.num_pages = num_pages
        self.record_times = record_times
        self.prefix_cache = prefix_cache
        self.prefill_chunk = prefill_chunk
        self.draft_executor = draft_executor
        self.spec_k = spec_k
        self.queue: deque = deque()
        # metrics registry is always live (it *is* the stats storage);
        # span tracing and drift pricing are the opt-in hooks
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.stats = EngineStats(self.metrics)
        self.tracer = tracer
        self._trace = tracer if (tracer is not None and tracer.enabled) else None
        self._tracks = (RequestTracks(self._trace)
                        if self._trace is not None else None)
        self._step = 0  # engine.step number, running across run() calls
        self.drift = drift
        if drift is not None and drift.registry is None:
            drift.registry = self.metrics
        # post-run introspection (tests / benches / demos)
        self.prefix_stats: Optional[Dict] = None

    def reset_stats(self) -> None:
        """Zero the per-run stats scope (counters, gauges, histograms).

        A reused engine accumulates stats across ``run()`` calls — call
        this between runs to scope ``engine.stats`` /
        ``engine.metrics.snapshot()`` to the next run only.  The lifetime
        scope (``engine.metrics.snapshot(scope="lifetime")``) keeps
        accumulating across resets.
        """
        self.metrics.reset_run()
        self.prefix_stats = None

    # --- request intake ---------------------------------------------------
    def submit(self, req: Request):
        if self.record_times:
            req.submit_time = time.perf_counter()
        self.queue.append(req)
        self.stats["requests"] += 1
        if self._tracks is not None:
            self._tracks.submit(req.uid)
        self.metrics.gauge("queue_depth").set(len(self.queue))

    def run(self) -> List[Request]:
        """Drain the queue; returns all completed requests."""
        mode = self.scheduler
        if mode == "auto":
            mode = ("continuous"
                    if getattr(self.executor, "supports_paged", False) else "wave")
        if mode == "continuous":
            return self._run_continuous()
        if self.spec_k is not None:
            raise ValueError(
                "speculative decoding requires the continuous scheduler, "
                "but this executor only supports the wave path"
            )
        if self.prefix_cache or self.prefill_chunk:
            raise ValueError(
                "prefix caching / chunked prefill belong to the continuous "
                "scheduler (the wave path has no paged pool to share)"
            )
        return self._run_waves()

    # --- shared helpers ---------------------------------------------------
    @property
    def _pad_multiple(self) -> int:
        return getattr(self.executor, "prompt_pad_multiple", 1)

    def _sample(self, logits):
        with span("engine.sample", self._trace):
            self.rng, key = jax.random.split(self.rng)
            return sample(logits, key, self.sampler)

    def _sample_positions(self, logits):
        """Per-position sampling for the speculative verify chunk.  Greedy
        (the only mode speculation runs in) consumes no randomness, so the
        RNG split never perturbs token pinning."""
        with span("engine.sample", self._trace):
            self.rng, key = jax.random.split(self.rng)
            return sample_positions(logits, key, self.sampler)

    def _emit(self, r: Request, token: int, limit: int) -> bool:
        """Append one token; returns True if the request just finished.

        The per-token hot path: no telemetry calls live here — TTFT/ITL
        histograms fill from the ``token_times`` stamps at retirement
        (:meth:`_retire_obs`), and tracing marks step boundaries, not
        tokens.
        """
        r.output.append(token)
        if self.record_times:
            r.token_times.append(time.perf_counter())
        if (r.eos_id is not None and token == r.eos_id) or len(r.output) >= limit:
            r.done = True
            return True
        return False

    def _retire_obs(self, r: Request, **span_args) -> None:
        """Observability at request completion: close the request's span
        track and fill the latency histograms from its ``record_times``
        stamps (TTFT = first token - submit; ITL = consecutive gaps)."""
        if self._tracks is not None and self._tracks.is_open(r.uid):
            self._tracks.finish(r.uid, tokens=len(r.output), **span_args)
        if r.submit_time is not None and r.token_times:
            self.metrics.histogram(
                "ttft_s", "time to first token (s)",
            ).observe(r.token_times[0] - r.submit_time)
            itl = self.metrics.histogram("itl_s", "inter-token latency (s)")
            ts = r.token_times
            for a, b in zip(ts, ts[1:]):
                itl.observe(b - a)

    def _pool_gauges(self, pool: PagedKVPool) -> None:
        """KV-pool gauges, updated at admission/retirement boundaries (not
        per decode step — occupancy between admissions moves by at most the
        pages the live slots grow into)."""
        m = self.metrics
        used = pool.used_pages
        m.gauge("kv_pages_used").set(used)
        m.gauge("kv_pool_occupancy", "used / usable pool pages").set(
            pool.occupancy())
        m.gauge("kv_pages_peak", "peak pages used").set_max(used)
        m.gauge("kv_shared_pages").set(pool.shared_page_count())

    # --- continuous batching over the paged pool --------------------------
    def _run_continuous(self) -> List[Request]:
        ex = self.executor
        if not getattr(ex, "supports_paged", False):
            raise ValueError(
                "continuous scheduler needs the paged executor protocol"
            )
        ps = self.page_size
        n_slots = self.max_batch
        # prompts pad to lcm(executor multiple, page size): page-boundary
        # padding costs no extra pages (allocation is page-granular anyway)
        # and bounds the number of distinct prefill shapes — one compiled
        # program per page count instead of one per prompt length.  The
        # same grain aligns prefix-cache hits and prefill chunks, so every
        # suffix chunk starts on a compile-shape boundary.
        grain = math.lcm(self._pad_multiple, ps)
        pad_max = _roundup(self.max_len, grain)
        pages_per_slot = pad_max // ps
        total_pages = self.num_pages or (1 + n_slots * pages_per_slot)
        pool = PagedKVPool(total_pages, ps, n_slots, pages_per_slot)
        storage = ex.make_pool(total_pages, ps)
        pcache = PrefixCache(pool, grain=grain) if self.prefix_cache else None
        self.pool = pool  # introspection (tests / benches)
        # telemetry locals: with `tr` None every span site below is a bare
        # profiler annotation, so a run without a tracer executes zero
        # Tracer instructions (gated structurally in tests/test_obs.py)
        tr = self._trace
        tracks = self._tracks
        drift = self.drift
        self._pool_gauges(pool)
        spec = None
        if self.spec_k is not None:
            # the draft pool mirrors the target pool's geometry so slot
            # indices and position arithmetic are shared between the two
            spec = SpeculativeDecoder(
                self.draft_executor, self.spec_k, num_slots=n_slots,
                page_size=ps, pages_per_slot=pages_per_slot,
            )
            self.spec = spec  # introspection (tests / benches)
        chunk_tokens = (None if self.prefill_chunk is None
                        else _roundup(self.prefill_chunk, grain))
        slots: List[Optional[_Slot]] = [None] * n_slots
        prefills: deque = deque()  # admitted slots still mid-prefill
        finished: List[Request] = []

        def prefill_step(t: _PrefillTask) -> bool:
            """Advance one chunk; True when the prompt is fully prefilled.

            The final chunk always covers position ``s - 1`` (chunk starts
            are grain-aligned and ``s_pad - s < grain``), so its logits row
            is the last real prompt token's — the first sampled token."""
            nonlocal storage
            off = t.next_off
            size = (t.s_pad - off if chunk_tokens is None
                    else min(chunk_tokens, t.s_pad - off))
            t0 = time.perf_counter() if drift is not None else 0.0
            with span("engine.prefill", tr, uid=t.req.uid, offset=off,
                      rows=size):
                block_row = jnp.asarray(pool.block_table[t.slot])
                chunk = jnp.asarray(t.tokens[:, off:off + size])
                if off == 0 and size == t.s_pad:
                    # one-shot program (no context gather): the
                    # pre-chunking path
                    logits, storage = ex.prefill_paged(
                        chunk, storage, block_row, length=t.s)
                else:
                    logits, storage = ex.prefill_chunk(
                        chunk, storage, block_row, offset=off, length=t.s)
                    # chunk logits carry every row; the sampled first token
                    # comes from the last *real* prompt token's row
                    logits = logits[:, max(0, min(t.s - 1 - off, size - 1))]
                    self.stats["prefill_chunks"] += 1
            if drift is not None:
                # drift is a diagnostics mode: mid-prompt chunks have no
                # natural sync point, so pricing their wall time costs one
                # block_until_ready here (the tracer alone never syncs)
                jax.block_until_ready(logits)
                drift.observe("prefill_chunk", time.perf_counter() - t0,
                              rows=size, context=off + size)
            # count *computed* prompt tokens: suffix-only under prefix hits
            self.stats["prefill_tokens"] += max(0, min(t.s, off + size) - off)
            t.next_off = off + size
            if t.next_off < t.s_pad:
                return False
            if pcache is not None:
                # publish this prompt's full pages for later admissions
                # (the partial tail page stays slot-private); the refcount
                # algebra is verified at sharing admissions and end of run
                pcache.insert(t.req.prompt, pool.block_table[t.slot])
            sampled = self._sample(logits)
            with span("engine.sync", tr):
                tok = int(np.asarray(sampled)[0])
            if self._emit(t.req, tok, t.limit):
                pool.retire(t.slot)
                finished.append(t.req)
                self._retire_obs(t.req)
                self._pool_gauges(pool)
            else:
                slots[t.slot] = _Slot(t.req, tok, t.s, t.limit)
                if tracks is not None:
                    tracks.phase(t.req.uid, "decode")
                if spec is not None:
                    spec.admit(t.slot, t.tokens, t.s,
                               max_positions=max(t.s_pad, t.s + t.limit))
            return True

        def admit() -> None:
            with span("engine.admit", tr) as sp:
                admitted = admit_pass()
                sp.set_metadata(admitted=admitted, queue=len(self.queue))

        def admit_pass() -> int:
            """Admission: prefix lookup -> shared-page refcount bump ->
            suffix-only prefill (inline, or queued as chunk tasks).
            Returns the number of requests admitted."""
            admitted = 0
            while self.queue:
                slot = pool.free_slot()
                if slot is None:
                    return admitted
                r = self.queue[0]
                s = len(r.prompt)
                limit = min(r.max_new_tokens, self.max_len - s)
                if limit <= 0:  # no room to decode even one token
                    self.queue.popleft()
                    r.done = True
                    finished.append(r)
                    self._retire_obs(r, rejected=True)
                    self.metrics.gauge("queue_depth").set(len(self.queue))
                    continue
                s_pad = _roundup(s, grain)
                max_positions = max(s_pad, s + limit)
                shared: List[int] = []
                cached = 0
                if pcache is not None:
                    with span("engine.prefix_lookup", tr, uid=r.uid) as sp:
                        shared, cached = pcache.lookup(r.prompt)
                        sp.set_metadata(cached_tokens=cached,
                                        shared_pages=len(shared))
                if not pool.can_admit(max_positions, shared=len(shared)):
                    if pcache is not None:
                        need = (pool.pages_for(max_positions) - len(shared)
                                - pool.available)
                        pcache.evict(need)
                        # eviction may have pruned our own match: re-walk
                        shared, cached = pcache.lookup(r.prompt)
                    if not pool.can_admit(max_positions, shared=len(shared)):
                        return admitted
                self.queue.popleft()
                admitted += 1
                pool.admit(slot, initial_positions=s_pad,
                           max_positions=max_positions, shared_pages=shared)
                self.metrics.gauge("queue_depth").set(len(self.queue))
                if tracks is not None:
                    tracks.phase(r.uid, "prefill", slot=slot,
                                 cached_tokens=cached)
                self._pool_gauges(pool)
                if shared:
                    self.stats["prefix_hits"] += 1
                    self.stats["cached_prefix_tokens"] += cached
                    self.stats["peak_shared_pages"] = max(
                        self.stats["peak_shared_pages"],
                        pool.shared_page_count())
                    pool.check()
                tokens = np.zeros((1, s_pad), np.int32)
                tokens[0, :s] = r.prompt
                task = _PrefillTask(r, slot, tokens, s, s_pad, limit,
                                    next_off=cached)
                if chunk_tokens is None:
                    # no interleaving requested: prefill to completion now
                    while not prefill_step(task):
                        pass
                else:
                    prefills.append(task)
            return admitted

        admit()
        while any(slots) or prefills or self.queue:
            # one iteration: a prefill chunk, then a decode step or a
            # speculative round, then the admission it frees room for
            with span("engine.step", tr, step=self._step):
                self._step += 1
                if not any(slots) and not prefills:
                    # nothing active and nothing admissible: drop the whole
                    # prefix tree (its pins may be what starves the head
                    # request) and retry before declaring the pool too small
                    if pcache is not None and len(pcache):
                        pcache.evict(total_pages)
                        admit()
                        if any(slots) or prefills:
                            continue
                    r = self.queue[0]
                    raise RuntimeError(
                        f"request uid={r.uid} (prompt {len(r.prompt)}, "
                        f"max_new {r.max_new_tokens}) cannot fit the pool of "
                        f"{total_pages} pages x {ps}"
                    )
                if prefills:
                    # one chunk per iteration, interleaved with the decode
                    # step below: long prompts no longer stall live slots
                    if prefill_step(prefills[0]):
                        prefills.popleft()
                live = [i for i, sl in enumerate(slots) if sl is not None]
                if live and spec is not None:
                    # speculative round: draft proposes (batched), the target
                    # verifies each slot's proposals in one chunk prefill,
                    # rejections roll back by block-table truncation
                    storage, done = run_spec_round(
                        self, spec, slots, live, pool, storage)
                    for i, req in done:
                        slots[i] = None
                        finished.append(req)
                        self._retire_obs(req)
                    if done:
                        self._pool_gauges(pool)
                elif live:
                    with span("engine.decode.prepare", tr):
                        tokens = np.zeros((n_slots, 1), np.int32)
                        positions = np.zeros(n_slots, np.int32)
                        live_mask = np.zeros(n_slots, bool)
                        for i in live:
                            pool.ensure(i, slots[i].next_index)
                            tokens[i, 0] = slots[i].last_token
                            positions[i] = slots[i].next_index
                            live_mask[i] = True
                        # non-live rows (idle *or mid-prefill*) decode
                        # against the null page: their dummy write must not
                        # touch real pages
                        bt = np.where(live_mask[:, None], pool.block_table, 0)
                        tokens_d, bt_d, positions_d = (
                            jnp.asarray(tokens), jnp.asarray(bt),
                            jnp.asarray(positions))
                    t0 = time.perf_counter() if drift is not None else 0.0
                    with span("engine.decode.dispatch", tr, live=len(live)):
                        logits, storage = ex.decode_paged(
                            tokens_d, storage, bt_d, positions_d)
                    self.stats["decode_steps"] += 1
                    self.stats["decode_tokens"] += len(live)
                    sampled = self._sample(logits)
                    with span("engine.sync", tr):
                        toks = np.asarray(sampled)
                    if drift is not None:
                        # sampling already synced the step: measured time is
                        # wall time with no extra block_until_ready
                        drift.observe("decode", time.perf_counter() - t0,
                                      rows=1,
                                      context=int(positions[live].max()) + 1)
                    with span("engine.emit", tr):
                        retired = False
                        for i in live:
                            sl = slots[i]
                            if self._emit(sl.req, int(toks[i]), sl.limit):
                                pool.retire(i)
                                slots[i] = None
                                finished.append(sl.req)
                                self._retire_obs(sl.req)
                                retired = True
                            else:
                                sl.last_token = int(toks[i])
                                sl.next_index += 1
                        if retired:
                            self._pool_gauges(pool)
                admit()  # freed slots refill at once: continuous batching
        # (spec_acceptance is derived on read by the stats facade)
        if pcache is not None:
            pool.check()  # final refcount-algebra validation for the run
            self.prefix_stats = pcache.stats()
            pcache.publish(self.metrics)
        else:
            self.prefix_stats = None
        return finished

    # --- wave execution ------------------------------------------------------
    def _bucket_len(self, prompt_len: int) -> int:
        """Wave bucket key: prompt length rounded up to the executor's
        padding multiple, so e.g. 11- and 12-token prompts share a wave on a
        4-device mesh while a single-device executor buckets exact lengths."""
        return _roundup(prompt_len, self._pad_multiple)

    def _next_wave(self) -> List[Request]:
        """Take up to max_batch queued requests from the largest bucket."""
        if not self.queue:
            return []
        buckets: Dict[int, List[Request]] = defaultdict(list)
        for r in self.queue:
            buckets[self._bucket_len(len(r.prompt))].append(r)
        _, reqs = max(buckets.items(), key=lambda kv: len(kv[1]))
        wave = reqs[: self.max_batch]
        # one-pass rebuild (deque.remove in a loop is O(n^2) and reorders
        # FIFO ties badly under load)
        taken = {id(r) for r in wave}
        self.queue = deque(r for r in self.queue if id(r) not in taken)
        return wave

    def _run_waves(self) -> List[Request]:
        finished: List[Request] = []
        while self.queue:
            wave = self._next_wave()
            if not wave:
                break
            self.metrics.gauge("queue_depth").set(len(self.queue))
            finished.extend(self._run_wave(wave))
        return finished

    def _run_wave(self, wave: List[Request]) -> List[Request]:
        tr = self._trace
        tracks = self._tracks
        # zero-budget requests (max_new_tokens=0, prompt filling or exceeding
        # max_len) never emit and never prefill, matching the continuous
        # path's admission-time retirement — an oversized prompt must not
        # reach the executor, whose cache only holds max_len positions
        for r in wave:
            if min(r.max_new_tokens, self.max_len - len(r.prompt)) <= 0:
                r.done = True
                self._retire_obs(r, rejected=True)
        live = [r for r in wave if not r.done]
        if not live:
            return wave
        b = len(live)
        lengths = np.array([len(r.prompt) for r in live], np.int32)
        limits = np.minimum([r.max_new_tokens for r in live],
                            self.max_len - lengths)
        budget = int(limits.max())
        uniform = int(lengths.min()) == int(lengths.max())
        s_pad = int(lengths[0]) if uniform else self._bucket_len(int(lengths.max()))

        tokens = np.zeros((b, s_pad), np.int32)
        for i, r in enumerate(live):
            tokens[i, : lengths[i]] = r.prompt
        cache = self.executor.make_cache(b, self.max_len)
        if tracks is not None:
            for r in live:
                tracks.phase(r.uid, "prefill", wave=True)
        with span("engine.prefill", tr, batch=b, rows=s_pad):
            if uniform:
                logits, cache = self.executor.prefill(jnp.asarray(tokens),
                                                      cache)
            else:
                logits, cache = self.executor.prefill(
                    jnp.asarray(tokens), cache, lengths=jnp.asarray(lengths)
                )
        self.stats["prefill_tokens"] += int(lengths.sum())
        if tracks is not None:
            # the wave decodes in lockstep: per-request decode phases open
            # together once the (joint) prefill is dispatched
            for r in live:
                tracks.phase(r.uid, "decode")

        active = np.ones(b, bool)
        for step in range(budget):
            next_tok = self._sample(logits)
            with span("engine.sync", tr):
                next_np = np.asarray(next_tok)
            with span("engine.emit", tr):
                for i, r in enumerate(live):
                    if not active[i]:
                        continue
                    if self._emit(r, int(next_np[i]), int(limits[i])):
                        active[i] = False
                        self._retire_obs(r)
            if not active.any():
                break
            if uniform:
                index = jnp.int32(int(lengths[0]) + step)
            else:
                # clamp retired slots that out-ran their own length budget;
                # their writes land in a dead cache row and are never read
                index = jnp.asarray(
                    np.minimum(lengths + step, self.max_len - 1), jnp.int32
                )
            with span("engine.decode.dispatch", tr, live=int(active.sum())):
                logits, cache = self.executor.decode(next_tok[:, None], cache,
                                                     index)
            self.stats["decode_steps"] += 1
            self.stats["decode_tokens"] += int(active.sum())
        for i, r in enumerate(live):
            r.done = True
            if active[i]:  # safety: budget exhausted before _emit finished it
                self._retire_obs(r)
        return wave
