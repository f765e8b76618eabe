"""Galaxy HMP executor: serve through the paper-exact schedule.

Bridges the serving engine (``serving/engine.py``) and the heterogeneity-
aware HMP executor (``core/hmp.py``): prefill runs the full TP/SP + ring
program sequence-sharded over the mesh, decode runs the single-token TP
step against the head-sharded KV cache — both under the same uneven
``ExecPlan`` the planner produced.

Both scheduler protocols are implemented.  Wave: ``make_cache`` /
``prefill`` / ``decode`` against a dense per-wave cache.  Paged
(continuous batching): ``make_pool`` / ``prefill_paged`` / ``decode_paged``
against a pool of head-sharded KV pages (``hmp.make_paged_kv_cache``:
``(pages, page, heads * head_dim)`` per layer, each position one row of
lanes, in the layout every paged program writes and reads) — prefill
scatters prompt KV straight into this request's pages, decode gathers each
slot's pages through the block table *inside* the shard_map, so every
device only ever touches its own head shard of the pool.  The paged
programs are built by ``paged_prefill_program`` / ``chunk_prefill_program``
/ ``paged_decode_program`` from the mesh and plan alone, so they can be
lowered for a described chip without weights.
``prefill_chunk`` extends the paged protocol for the engine's shared-prefix
admission flow (lookup -> refcount bump -> suffix-only chunked prefill) and
chunked prefill: a chunk starts at an arbitrary grain-aligned offset and
attends back to the KV pages already holding the shared prefix and earlier
chunks, so a prefix hit pays compute only for the uncached suffix.

Sequence layout is plan-derived: prefill scatters the prompt into the
plan's padded ragged layout (``ExecPlan.seq_layout`` — per-device sequence
tiles at per-device offsets, padded to the straggler's tile), so uneven
*sequence* plans run end to end and no prompt length depends on mesh
divisibility for correctness.  ``prompt_pad_multiple`` (the engine's
padding policy hook) is likewise plan-derived (``ExecPlan.seq_grain``) and
now only buckets prompt lengths to bound the number of compiled prefill
shapes.  K/V are written at absolute positions, and each decode step
overwrites its own cache slot/page entry before attending, so bucket
padding rows are never read.

Every call into a compiled program runs inside a ``galaxy.prefill`` or
``galaxy.decode`` profiler span (``repro.obs.trace.span``): the executor's
own host time, pytree flattening of the weights and dispatch, on the device
trace's clock.

``GalaxyMLAExecutor`` serves a second block, the DeepSeek-V3 layer
(``mla_moe.MLALayer``: latent attention, dense or mixture-of-experts
feed-forward, with a ``mla_moe.Vocab`` of embedding, final norm and untied
head), through the paged protocol alone, on a one-device plan, with a
latent pool (``mla_moe.make_latent_pool``).  Its programs
(``mla_prefill_program``, ``mla_decode_program``) also return the chosen
expert ids, kept as ``routing`` after each call, and add each held expert's
assignments to ``moe_load`` on the device.  Its counters, in ``metrics``:
``moe.tokens_routed`` and ``mla.latent_positions_read`` per call (host
arithmetic on shapes), ``moe.assignments_here`` when ``read_moe_load``
reads the per-expert counts back; its ``galaxy.decode`` spans carry the
call's number as ``call``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hmp, mla_moe
from repro.core.execplan import ExecPlan
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import span


class GalaxyHMPExecutor:
    """Executor protocol over HMP layers (wave + paged serving).

    layers: stack of layer params in *reference* layout (init_layer_params);
            padded once here via ``plan.pad_layer_params``.
    embed:  (vocab, d_model) tied embedding / unembedding table.
    compute_backend: overrides the plan's per-shard compute path
            (``execplan.COMPUTE_BACKENDS``): "xla" is the padded dense
            oracle, "pallas" sheds pad-block work in every prefill/decode
            matmul (and the prefill attention) via ``kernels/ops.py``.
    transport / double_buffer: override the plan's ring transport
            (``ring.RING_TRANSPORTS``): "bucketed" ships each ring hop at
            its tile's bucketed row count instead of the straggler pad,
            and ``double_buffer=True`` issues step k+1's exchange before
            step k's GEMM so the wire hides under compute.  Both leave
            results bitwise-identical to the padded ring.
    """

    def __init__(self, layers: Sequence[Dict], embed, plan: ExecPlan,
                 mesh: Mesh, *, overlap: bool = True,
                 compute_backend: Optional[str] = None,
                 transport: Optional[str] = None,
                 double_buffer: Optional[bool] = None):
        if compute_backend is not None:
            plan = plan.with_backend(compute_backend)
        if transport is not None or double_buffer is not None:
            plan = plan.with_transport(transport, double_buffer=double_buffer)
        self.plan = plan
        self.mesh = mesh
        self.overlap = overlap
        self._place(layers, embed)
        self._prefill_fns: Dict = {}
        self._decode_fn = None
        self._decode_paged_fn = None

    def _place(self, layers, embed) -> None:
        # weights live sharded over the mesh once, as every step reads them
        # (head/column shards per device; the embedding is replicated)
        specs = hmp.layer_param_specs()
        self.layers = [
            jax.device_put(self.plan.ensure_padded(p),
                           {k: NamedSharding(self.mesh, specs[k]) for k in p})
            for p in layers
        ]
        self.embed = jax.device_put(jnp.asarray(embed),
                                    NamedSharding(self.mesh, P()))

    # --- padding policy -------------------------------------------------------
    @property
    def prompt_pad_multiple(self) -> int:
        """Plan-derived prompt bucketing grain.  The ragged SP layout makes
        any length correct; bucketing only bounds compiled prefill shapes."""
        return self.plan.seq_grain

    # --- wave protocol --------------------------------------------------------
    def make_cache(self, batch: int, max_len: int) -> List[Dict]:
        # cache rows are *absolute* positions (ragged prefill gathers valid
        # rows before writing), so the cache only needs the largest bucketed
        # prompt length — not the plan's padded ragged extent, which for a
        # strongly uneven seq split would over-allocate KV by max(frac)*D
        grain = self.plan.seq_grain
        cache_len = -(-max_len // grain) * grain
        return hmp.make_kv_cache(
            batch, cache_len, len(self.layers), self.mesh, self.plan,
            dtype=self.embed.dtype,
        )

    def prefill(self, tokens, cache, lengths=None):
        """Prefill a wave.  ``lengths`` (B,) gathers each row's last real
        logit when the wave mixes prompt lengths (rows right-padded).

        The prompt is scattered into the plan's padded ragged layout at
        per-device offsets (identity for an equal split of a dividing
        length) and the output gathered back, so uneven sequence tiles and
        non-dividing lengths run exactly."""
        b, s = tokens.shape
        key = (b, s, lengths is not None)
        if key not in self._prefill_fns:
            layout = self.plan.seq_layout(s)
            mesh, plan, overlap = self.mesh, self.plan, self.overlap

            def prefill(layers, embed, tokens, cache, lengths=None):
                tokens = layout.scatter(tokens)  # identity when dense
                x = embed[tokens]  # (B, padded, d)
                y, cache = hmp.hmp_prefill(
                    layers, x, mesh, cache, plan=plan, overlap=overlap, seq=s
                )
                y = layout.gather(y)  # back to real positions
                if lengths is None:
                    logits = y[:, s - 1] @ embed.T
                else:
                    logits = y[jnp.arange(b), lengths - 1] @ embed.T
                return logits, cache

            self._prefill_fns[key] = jax.jit(prefill)
        with span("galaxy.prefill"):
            if lengths is None:
                return self._prefill_fns[key](self.layers, self.embed, tokens,
                                              cache)
            return self._prefill_fns[key](
                self.layers, self.embed, tokens, cache, lengths
            )

    def decode(self, tokens, cache, index):
        if self._decode_fn is None:
            mesh, plan = self.mesh, self.plan

            def decode(layers, embed, tokens, cache, index):
                x = embed[tokens]  # (B, 1, d)
                y, cache = hmp.hmp_decode(layers, x, mesh, cache, index, plan=plan)
                logits = y[:, -1] @ embed.T
                return logits, cache

            self._decode_fn = jax.jit(decode)
        with span("galaxy.decode"):
            return self._decode_fn(self.layers, self.embed, tokens, cache,
                                   index)

    # --- paged protocol -------------------------------------------------------
    @property
    def supports_paged(self) -> bool:
        return True

    def make_pool(self, num_pages: int, page_size: int) -> List[Dict]:
        return hmp.make_paged_kv_cache(
            num_pages, page_size, len(self.layers), self.mesh, self.plan,
            dtype=self.embed.dtype,
        )

    def prefill_chunk(self, tokens, pool, block_row, *, offset, length):
        """One chunked-prefill step (batch 1): run a grain-aligned chunk of
        the prompt at absolute positions [offset, offset + S) through the
        Galaxy schedule, attending back to the pages already written by the
        shared prefix and earlier chunks (``hmp_prefill(offset=)`` gathers
        the block row as attention context inside the shard_map).
        Returns ``(logits, pool)`` with *every* chunk row's logits,
        (1, S, V): row ``j`` predicts position ``offset + j + 1``.  Chunked
        prompt prefill reads only the last real prompt token's row;
        speculative verification (``serving/spec.py``) compares all rows
        against the draft proposals."""
        key = ("chunk", tokens.shape[1])
        if key not in self._prefill_fns:
            self._prefill_fns[key] = chunk_prefill_program(
                self.mesh, self.plan, self.overlap, tokens.shape[1])
        with span("galaxy.prefill"):
            return self._prefill_fns[key](
                self.layers, self.embed, tokens, pool, block_row,
                jnp.asarray(offset, jnp.int32), jnp.asarray(length, jnp.int32),
            )

    def prefill_paged(self, tokens, pool, block_row, length: int):
        """Prefill one request (batch 1, tokens bucket-padded by the engine)
        writing prompt KV straight into this request's pool pages."""
        key = ("paged", tokens.shape[1])
        if key not in self._prefill_fns:
            self._prefill_fns[key] = paged_prefill_program(
                self.mesh, self.plan, self.overlap, tokens.shape[1])
        with span("galaxy.prefill"):
            return self._prefill_fns[key](
                self.layers, self.embed, tokens, pool, block_row,
                jnp.asarray(length, jnp.int32),
            )

    def decode_paged(self, tokens, pool, block_table, positions):
        if self._decode_paged_fn is None:
            self._decode_paged_fn = paged_decode_program(self.mesh, self.plan)
        with span("galaxy.decode"):
            return self._decode_paged_fn(
                self.layers, self.embed, tokens, pool, block_table, positions
            )


class GalaxyMLAExecutor(GalaxyHMPExecutor):
    """The paged protocol over a stack of ``mla_moe.MLALayer`` (the
    DeepSeek-V3 block) and its ``mla_moe.Vocab``, on a one-device plan.

    Serves through the engine's paged entry points only: a multi-device
    plan, or the wave protocol's dense cache, raises.  Each call keeps the
    program's expert ids as ``routing`` and adds its held experts'
    assignments to ``moe_load`` on the device."""

    def _place(self, layers: Sequence[mla_moe.MLALayer],
               vocab: mla_moe.Vocab) -> None:
        if self.plan.num_devices != 1:
            raise NotImplementedError(
                f"the MLA/MoE block runs on a one-device plan; this plan has "
                f"{self.plan.num_devices} devices (no four-chip cell judges "
                "its head and expert shards yet)")
        self.layers = [jax.device_put(l, mla_moe.layer_shardings(l, self.mesh))
                       for l in layers]
        self.embed = jax.device_put(vocab, NamedSharding(self.mesh, P()))
        self.moe_load = jnp.zeros((mla_moe.moe_layers(layers),
                                   mla_moe.experts_held(layers)), jnp.int32)
        self.routing = None
        self.metrics = MetricsRegistry()
        self._calls = 0

    def _count(self, rows: int, read: int) -> None:
        m = self.metrics
        m.counter("moe.tokens_routed", "token-layer pairs routed").inc(
            rows * self.moe_load.shape[0])
        m.counter("mla.latent_positions_read",
                  "pool rows read, per layer").inc(read * len(self.layers))

    def read_moe_load(self) -> np.ndarray:
        """Each held expert's assignments so far, (moe_layers, held): one
        read from the device; sets ``moe.assignments_here``."""
        load = np.asarray(self.moe_load)
        c = self.metrics.counter("moe.assignments_here",
                                 "token-expert pairs computed on this chip")
        c.inc(int(load.sum()) - c.value)
        return load

    def make_cache(self, batch: int, max_len: int) -> List[Dict]:
        raise NotImplementedError(
            "the MLA/MoE block serves through the paged protocol only")

    def make_pool(self, num_pages: int, page_size: int) -> List[Dict]:
        return mla_moe.make_latent_pool(num_pages, page_size, self.layers,
                                        self.mesh)

    def prefill_chunk(self, tokens, pool, block_row, *, offset, length):
        """A chunk at absolute positions [offset, offset + S) attending back
        to the block row; every row's logits, (1, S, V)."""
        read = block_row.shape[0] * pool[0]["c"].shape[1]
        return self._prefill(("chunk", tokens.shape[1]), tokens, pool,
                             block_row, length, offset, read=read)

    def prefill_paged(self, tokens, pool, block_row, length: int):
        return self._prefill(("paged", tokens.shape[1]), tokens, pool,
                             block_row, length)

    def _prefill(self, key, tokens, pool, block_row, length, offset=None,
                 read: int = 0):
        if key not in self._prefill_fns:
            self._prefill_fns[key] = mla_prefill_program(
                self.mesh, tokens.shape[1], chunk=offset is not None)
        args = () if offset is None else (jnp.asarray(offset, jnp.int32),)
        with span("galaxy.prefill"):
            logits, pool, self.routing, self.moe_load = \
                self._prefill_fns[key](
                    self.layers, self.embed, tokens, pool, block_row,
                    jnp.asarray(length, jnp.int32), self.moe_load, *args)
        self._count(tokens.shape[1], read)
        return logits, pool

    def decode_paged(self, tokens, pool, block_table, positions):
        if self._decode_paged_fn is None:
            self._decode_paged_fn = mla_decode_program(self.mesh)
        with span("galaxy.decode", call=self._calls):
            logits, pool, self.routing, self.moe_load = self._decode_paged_fn(
                self.layers, self.embed, tokens, pool, block_table, positions,
                self.moe_load)
        self._calls += 1
        s, w = block_table.shape
        self._count(s, s * w * pool[0]["c"].shape[1])
        return logits, pool


# --- paged programs -----------------------------------------------------------
# Each takes (layers, embed, tokens, pool, ...) and donates the pool, so the
# page scatter happens in place in the pool's own layout.

def paged_prefill_program(mesh: Mesh, plan: ExecPlan, overlap: bool, s: int):
    """Jitted prefill of one request (batch 1, ``s`` bucket-padded tokens)
    writing prompt KV straight into its pool pages; returns the logits of
    the last real prompt token and the pool."""
    layout = plan.seq_layout(s)

    # length stays a traced scalar so every prompt sharing this padded shape
    # reuses one compiled program
    def prefill(layers, embed, tokens, pool, block_row, length):
        tokens = layout.scatter(tokens)  # identity when dense
        x = embed[tokens]  # (1, padded, d)
        y, pool = hmp.hmp_prefill(layers, x, mesh, pool, plan=plan,
                                  overlap=overlap, seq=s, block_row=block_row)
        y = layout.gather(y)
        logits = y[:, length - 1] @ embed.T
        return logits, pool

    return jax.jit(prefill, donate_argnums=(3,))


def chunk_prefill_program(mesh: Mesh, plan: ExecPlan, overlap: bool, s: int):
    """Jitted chunked prefill of ``s`` tokens at a traced absolute offset,
    attending back to the block row's written pages; returns every chunk
    row's logits (1, S, V) and the pool."""
    layout = plan.seq_layout(s)

    # offset/length stay traced scalars: one compiled program per chunk
    # shape, shared by every offset it runs at
    def prefill(layers, embed, tokens, pool, block_row, offset, length):
        tokens = layout.scatter(tokens)  # identity when dense
        x = embed[tokens]  # (1, padded, d)
        y, pool = hmp.hmp_prefill(layers, x, mesh, pool, plan=plan,
                                  overlap=overlap, seq=s, block_row=block_row,
                                  offset=offset)
        y = layout.gather(y)
        logits = y @ embed.T  # (1, S, V): all chunk rows
        return logits, pool

    return jax.jit(prefill, donate_argnums=(3,))


def paged_decode_program(mesh: Mesh, plan: ExecPlan):
    """Jitted slot-batch decode step: (S, 1) tokens, (S, W) block table,
    (S,) positions; returns (S, V) logits and the pool."""
    def decode(layers, embed, tokens, pool, block_table, positions):
        x = embed[tokens]  # (S, 1, d)
        y, pool = hmp.hmp_decode(layers, x, mesh, pool, positions, plan=plan,
                                 block_table=block_table)
        logits = y[:, -1] @ embed.T
        return logits, pool

    return jax.jit(decode, donate_argnums=(3,))


def mla_prefill_program(mesh: Mesh, s: int, *, chunk: bool = False):
    """Jitted prefill of one request (``s`` bucket-padded tokens) through an
    ``MLALayer`` stack into its latent pool pages.  Plain: the logits of the
    last real prompt token (1, V); ``chunk``: a chunk at a traced absolute
    offset (last argument) attending back to the block row, every row's
    logits (1, S, V).  Also returns the chosen expert ids and the per-expert
    load (donated) plus this call's assignments."""
    def prefill(layers, vocab, tokens, pool, block_row, length, load,
                offset=None):
        x = vocab.table[tokens[0]]  # (S, d)
        y, pool, ids, sizes = mla_moe.prefill(layers, x, mesh, pool,
                                              block_row, offset=offset)
        eps = layers[0].block.eps
        logits = (mla_moe.head(vocab, y, eps)[None] if chunk
                  else mla_moe.head(vocab, y[length - 1], eps)[None])
        return logits, pool, ids, load + sizes

    return jax.jit(prefill, donate_argnums=(3, 6))


def mla_decode_program(mesh: Mesh):
    """Jitted slot-batch decode step of an ``MLALayer`` stack: (S, 1)
    tokens, (S, W) block table, (S,) positions; returns (S, V) logits, the
    pool, the chosen expert ids (moe_layers, S, k) and the per-expert load
    (donated) plus this step's assignments."""
    def decode(layers, vocab, tokens, pool, block_table, positions, load):
        x = vocab.table[tokens[:, 0]]  # (S, d)
        y, pool, ids, sizes = mla_moe.decode(layers, x, mesh, pool,
                                             block_table, positions)
        logits = mla_moe.head(vocab, y, layers[0].block.eps)
        return logits, pool, ids, load + sizes

    return jax.jit(decode, donate_argnums=(3, 6))
