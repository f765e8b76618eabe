"""Hybrid Model Parallelism (paper §III-B) as explicit shard_map programs.

This module is the *faithful* executable of the paper's Fig. 5 on a
Transformer layer (post-LN, as in Fig. 2): TP over heads (MHA) and FFN
columns (MLP), SP over the connective blocks, with a ReduceScatter exiting
each TP block and an AllGather entering it.  Three schedules:

* ``hmp``       — Galaxy HMP, synchronous collectives (faithful baseline)
* ``hmp_ring``  — Galaxy HMP + tile-based ring overlap (paper §III-D)
* ``megatron``  — Megatron-LM TP baseline: AllReduce after each block,
                  connective blocks computed redundantly on every device
* ``sp``        — pure Sequence Parallelism baseline: weights replicated,
                  2 AllGathers (K and V) per MHA block

All four produce identical math (up to summation order); tests assert
allclose against the single-device reference.

Heterogeneity-aware execution: every entry point takes an optional
``plan: ExecPlan`` (``core/execplan.py``).  The plan materializes the
planner's *uneven* head/column assignment as padded-and-masked shards —
each device's slice padded to ``max(units)`` with zeroed weights, so the
math stays exact while per-device shapes stay SPMD-equal.  The SP axis is
uneven the same way: a plan with ragged ``seq_shares`` runs the sequence
in a padded ragged layout (``execplan.SeqLayout``) — real rows scattered
to per-device offsets, pad rows masked out of the ring schedule and the
attention mask, and K/V written to the cache at *absolute* positions so
decode never sees the padding.  Callers pass the logical length as
``seq=`` and the sequence pre-scattered via ``layout.scatter``; with an
equal split of a dividing length the layout is dense and the code path is
bit-identical to the pre-ragged one.  Without a plan the layer behaves as
before (even split, padded == real).

Pluggable per-shard compute (``ExecPlan.compute_backend``): with the
default ``"xla"`` backend the padded shards run dense einsums — every
device executes ``max(units)`` work, zeros included (the honesty cost
``ExecPlan.padding_waste()`` bookkeeps; this path is the correctness
oracle).  With ``"pallas"`` every per-shard matmul and the prefill
attention route through ``kernels/ops.py``: per-device valid head/column
counts enter the valid-length kernels as scalar-prefetch operands and the
grids *skip* pad blocks, so executed MXU work tracks the plan's assigned
units.  The decode attention core stays XLA (it is a block-table gather,
not an MXU-bound GEMM); its projections shed like everything else.
Pallas inside ``jax.shard_map`` needs ``check_vma=False`` (``pallas_call``
has no varying-manual-axes rule), so that flag flips only on the pallas
path and the xla graphs stay bit-identical to before.

Serving path: ``hmp_prefill`` / ``hmp_decode`` run a *stack* of layers
through the Galaxy schedule against a head-sharded KV cache — prefill is
the full TP/SP + ring program; decode is the single-token degenerate case
(pure TP with an AllReduce; an SP split of one token is meaningless), which
is what ``serving/galaxy.py`` drives from the wave scheduler.  One
keyword-normalized entry family covers every cache kind: ``seq=``,
``plan=``, the cache kind (dense, or paged via ``block_row=`` /
``block_table=``), and ``offset=`` compose orthogonally.
``hmp_prefill(..., block_row=)`` writes straight into pool pages
(continuous batching); adding ``offset=`` makes it the chunked/suffix-only
entry point — a chunk starting at an absolute offset attends back to the
KV pages already written by a shared prompt prefix
(``serving/prefix_cache.py``) and earlier chunks.  ``hmp_decode(...,
block_table=)`` is the paged slot-batch decode step.  (The pre-unification
``hmp_prefill_paged`` / ``hmp_decode_paged`` names were shimmed for one
release and have been removed.)

The ring side of every prefill runs a ``ring.RingSchedule`` built from the
plan (``ExecPlan.ring_schedule``): the plan's ``transport`` /
``double_buffer`` knobs select padded vs bucketed ragged transport and
explicit tile-level double buffering without touching this module's code
paths — the default padded single-buffer schedule keeps the exact
pre-schedule XLA graphs.

The production models use the GSPMD expression of the same layout
(models/sharding.py); this module is the paper-exact schedule used for
equivalence tests, benchmarks, and as the template for the perf work.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.execplan import ExecPlan, SeqLayout
from repro.core.ring import (
    RingSchedule,
    matmul_ring_reducescatter,
    ring_allgather_matmul,
    sync_allgather_matmul,
    sync_matmul_reducescatter,
)
from repro.kernels import ops

AXIS = "model"

# KV cache entries are (B, cache_len, heads, head_dim), head-sharded
CACHE_SPEC = P(None, None, AXIS, None)


# --- paper-style layer (Fig. 2): post-LN MHA + MLP --------------------------

def init_layer_params(key, d_model: int, num_heads: int, d_ff: int, dtype=jnp.float32) -> Dict:
    hd = d_model // num_heads
    ks = jax.random.split(key, 6)
    s = 0.02
    return {
        "wq": jax.random.normal(ks[0], (d_model, num_heads, hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d_model, num_heads, hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d_model, num_heads, hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (num_heads, hd, d_model), dtype) * s,
        "w1": jax.random.normal(ks[4], (d_model, d_ff), dtype) * s,
        "w2": jax.random.normal(ks[5], (d_ff, d_model), dtype) * s,
        "ln1_s": jnp.ones((d_model,), dtype),
        "ln1_b": jnp.zeros((d_model,), dtype),
        "ln2_s": jnp.ones((d_model,), dtype),
        "ln2_b": jnp.zeros((d_model,), dtype),
    }


def init_stack_params(key, num_layers: int, d_model: int, num_heads: int,
                      d_ff: int, dtype=jnp.float32) -> List[Dict]:
    keys = jax.random.split(key, num_layers)
    return [init_layer_params(k, d_model, num_heads, d_ff, dtype) for k in keys]


def layer_param_specs(megatron: bool = False, sp: bool = False) -> Dict:
    """PartitionSpecs for the layer params under each parallelism plan.

    Identical for even and ExecPlan-padded layouts: padding only changes the
    (divisible) global extent of the sharded axes, not which axes shard.
    """
    if sp:  # weights replicated
        return {k: P() for k in (
            "wq", "wk", "wv", "wo", "w1", "w2", "ln1_s", "ln1_b", "ln2_s", "ln2_b")}
    return {
        "wq": P(None, AXIS, None),
        "wk": P(None, AXIS, None),
        "wv": P(None, AXIS, None),
        "wo": P(AXIS, None, None),
        "w1": P(None, AXIS),
        "w2": P(AXIS, None),
        "ln1_s": P(), "ln1_b": P(), "ln2_s": P(), "ln2_b": P(),
    }


def _ln(x, s, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * s + b).astype(x.dtype)


def _attention(q, k, v, mask=None):
    """q,k,v: (B, S, H, hd) -> (B, S, H, hd).  ``mask`` overrides the plain
    causal mask — a ragged ``SeqLayout`` supplies causality in the padded
    domain, where pad rows interleave with real positions."""
    hd = q.shape[-1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / np.sqrt(hd)
    s, t = scores.shape[-2], scores.shape[-1]
    if mask is None:
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def reference_layer(p: Dict, x):
    """Single-device oracle of the paper's Fig. 2 layer (post-LN)."""
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    attn = _attention(q, k, v)
    g = jnp.einsum("bshk,hkd->bsd", attn, p["wo"])
    x = _ln(x + g, p["ln1_s"], p["ln1_b"])
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w1"]))
    f = jnp.einsum("bsf,fd->bsd", h, p["w2"])
    x = _ln(x + f, p["ln2_s"], p["ln2_b"])
    return x


def reference_stack(layers: Sequence[Dict], x):
    for p in layers:
        x = reference_layer(p, x)
    return x


# --- Galaxy HMP (shard_map) ---------------------------------------------------

class _PallasCompute:
    """Per-device ragged compute bindings (``compute_backend="pallas"``).

    Built *inside* the shard_map body: ``axis_index`` resolves this
    device's valid head/column counts, which enter the valid-length
    kernels (``kernels/ops.py``) as scalar-prefetch operands — the kernel
    grids skip blocks that are entirely padding, so each device's executed
    MXU work tracks its assigned ``units[d]``, not ``max(units)``.  The
    methods double as the ring primitives' per-tile ``gemm`` callbacks
    (``valid_rows`` is the held tile's real row count in ring order).
    """

    def __init__(self, plan: ExecPlan, positions: Optional[np.ndarray]):
        idx = jax.lax.axis_index(AXIS)
        self.hd = plan.head_dim
        self.pad_heads = plan.pad_heads
        self.valid_heads = jnp.asarray(plan.heads, jnp.int32)[idx]
        self.valid_cols = jnp.asarray(plan.columns, jnp.int32)[idx]
        self.positions = positions  # padded row -> real position (static)

    def qkv_gemm(self, tile, w, valid_rows=None):
        # w = [wq | wk | wv]: three column segments, each a padded head
        # slot block with this device's real heads as the valid prefix
        return ops.gemm(tile, w, backend="pallas", valid_m=valid_rows,
                        valid_n=self.valid_heads * self.hd,
                        seg_n=self.pad_heads * self.hd)

    def wo_gemm(self, tile, w, valid_rows=None):
        return ops.gemm(tile, w, backend="pallas", valid_m=valid_rows,
                        valid_k=self.valid_heads * self.hd)

    def w1_gemm(self, tile, w, valid_rows=None):
        return ops.gemm(tile, w, backend="pallas", valid_m=valid_rows,
                        valid_n=self.valid_cols)

    def w2_gemm(self, tile, w, valid_rows=None):
        return ops.gemm(tile, w, backend="pallas", valid_m=valid_rows,
                        valid_k=self.valid_cols)

    def attention(self, q, k, v):
        """(B, S, H, hd) ragged flash attention: pad rows and pad head
        slots are skipped and come out exactly zero."""
        return ops.ragged_attention(q, k, v, positions=self.positions,
                                    valid_heads=self.valid_heads)

    def connective(self, x, res, scale, bias):
        """Fused residual + layernorm (one HBM pass) == ``_ln(res + x)``."""
        return ops.connective(x, res, scale, bias)


def _make_compute(backend: str, plan: Optional[ExecPlan],
                  layout: Optional[SeqLayout],
                  seq_total: Optional[int]) -> Optional[_PallasCompute]:
    if backend != "pallas":
        return None
    if layout is not None:
        positions = layout.positions
    elif seq_total is not None:
        positions = np.arange(seq_total)
    else:
        positions = None  # decode: attention stays on the XLA gather path
    return _PallasCompute(plan, positions)


def _ctx_attention(q, k, v, ctx, layout: Optional[SeqLayout]):
    """Chunked-prefill attention: chunk queries attend to already-written
    context pages plus the chunk's own K/V.

    ``ctx = (ctx_k, ctx_v, offset)``: ctx_k/ctx_v are (T, h_loc, hd)
    block-row gathers over *absolute* positions [0, T); only positions
    ``< offset`` (shared prefix pages + earlier chunks) are unmasked, so
    stale/null-page rows never contribute — they are exact zeros after the
    softmax, which keeps chunked outputs equal to the one-shot prefill.
    The local (chunk) part keeps the usual causal/ragged mask: relative
    causality inside a chunk is offset-invariant, and every context key
    precedes every real chunk query (ctx_pos < offset <= q_pos)."""
    ctx_k, ctx_v, offset = ctx
    s, t = q.shape[1], ctx_k.shape[0]
    if layout is not None:
        local = jnp.asarray(layout.attention_mask())
    else:
        local = jnp.tril(jnp.ones((s, s), bool))
    ctx_mask = jnp.broadcast_to(jnp.arange(t)[None, :] < offset, (s, t))
    mask = jnp.concatenate([ctx_mask, local], axis=1)
    kf = jnp.concatenate([ctx_k[None].astype(k.dtype), k], axis=1)
    vf = jnp.concatenate([ctx_v[None].astype(v.dtype), v], axis=1)
    return _attention(q, kf, vf, mask=mask)


def _hmp_layer_local(p, x_loc, *, overlap: bool, return_kv: bool = False,
                     layout: Optional[SeqLayout] = None,
                     plan: Optional[ExecPlan] = None, backend: str = "xla",
                     ctx=None):
    """Body on one device.  x_loc: (B, S_loc, d) sequence shard; params are
    head/column shards (possibly ExecPlan-padded with zero weights).  TP
    blocks see the full sequence; connective blocks see the local shard
    (paper Fig. 5).  With ``return_kv`` also emits this device's K/V head
    shards over the full sequence, for prefilling a decode cache.

    ``layout`` (a *ragged* SeqLayout; dense layouts pass None) drives the
    uneven-SP masking: the ring primitives zero pad rows per step, and the
    attention mask encodes causality over the padded row order.  Garbage in
    pad rows stays confined to pad rows — LN and residuals are rowwise, the
    rings zero their pad inputs, and attention masks pad keys — so every
    valid row is exact.

    ``ctx`` (chunked prefill; see ``_ctx_attention``) makes the attention
    additionally read already-written KV pages: the chunk's queries attend
    to context keys at absolute positions below the chunk offset.  The
    attention core then takes the XLA path even under the pallas backend
    (like decode it is a page-gather, not a self-attention the ragged flash
    kernel covers); the TP GEMMs still shed pad blocks."""
    ag_mm = ring_allgather_matmul if overlap else sync_allgather_matmul
    mm_rs = matmul_ring_reducescatter if overlap else sync_matmul_reducescatter

    d_model = x_loc.shape[-1]
    s_loc = x_loc.shape[1]
    h_loc, hd = p["wq"].shape[1], p["wq"].shape[2]
    n_dev = plan.num_devices if plan is not None else None
    # the ring program (tile geometry, wire format, overlap mode) is solved
    # ahead of trace time from the plan; without a plan the primitives build
    # their own dense even-split schedule from the shard shapes
    if plan is None:
        base_sched = None
    elif layout is not None:
        base_sched = plan.ring_schedule(layout=layout)
    else:
        base_sched = RingSchedule.dense(
            n_dev, s_loc, transport=plan.transport,
            double_buffer=plan.double_buffer)

    def _sched(gemm_fn):
        return None if base_sched is None else base_sched.with_gemm(gemm_fn)

    compute = _make_compute(backend, plan, layout,
                            None if n_dev is None else n_dev * s_loc)
    # the O(padded_len^2) ragged mask feeds only the xla attention path;
    # the pallas path derives masking from layout.positions in-kernel
    attn_mask = None if (layout is None or compute is not None) \
        else jnp.asarray(layout.attention_mask())

    # ---- MHA block (TP over heads) ----
    wqkv = jnp.concatenate(
        [p["wq"].reshape(d_model, -1), p["wk"].reshape(d_model, -1),
         p["wv"].reshape(d_model, -1)], axis=1)
    qkv = ag_mm(x_loc, wqkv, AXIS,
                schedule=_sched(compute.qkv_gemm if compute else None))  # AllGather ⊗ GEMM1
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = (*q.shape[:2], h_loc, hd)
    k, v = k.reshape(shape), v.reshape(shape)
    if ctx is not None:
        attn = _ctx_attention(q.reshape(shape), k, v, ctx, layout)
    elif compute is not None:
        attn = compute.attention(q.reshape(shape), k, v)
    else:
        attn = _attention(q.reshape(shape), k, v, mask=attn_mask)
    attn = attn.reshape(*q.shape[:2], h_loc * hd)
    g_loc = mm_rs(attn, p["wo"].reshape(-1, d_model), AXIS,
                  schedule=_sched(compute.wo_gemm if compute else None))  # GEMM ⊗ ReduceScatter

    # ---- connective block (SP over local sequence shard) ----
    if compute is not None:
        y_loc = compute.connective(g_loc, x_loc, p["ln1_s"], p["ln1_b"])
    else:
        y_loc = _ln(x_loc + g_loc, p["ln1_s"], p["ln1_b"])

    # ---- MLP block (TP over columns) ----
    h = ag_mm(y_loc, p["w1"], AXIS,
              schedule=_sched(compute.w1_gemm if compute else None))
    h = jax.nn.gelu(h)
    f_loc = mm_rs(h, p["w2"], AXIS,
                  schedule=_sched(compute.w2_gemm if compute else None))

    # ---- connective block ----
    if compute is not None:
        out = compute.connective(f_loc, y_loc, p["ln2_s"], p["ln2_b"])
    else:
        out = _ln(y_loc + f_loc, p["ln2_s"], p["ln2_b"])
    if return_kv:
        return out, k, v
    return out


def _validate_plan(p: Dict, x, mesh: Mesh, plan: Optional[ExecPlan],
                   seq: Optional[int] = None):
    """Pad params and resolve the sequence layout for one entry point.

    Returns ``(params, layout)``; ``layout`` is None when there is no plan,
    no sequence, or the layout is dense (equal tiles fully covering the
    rows), so the dense path keeps its exact pre-ragged XLA graph."""
    n = mesh.shape[AXIS]
    layout = None
    if plan is not None:
        if plan.num_devices != n:
            raise ValueError(
                f"plan covers {plan.num_devices} devices but mesh axis "
                f"'{AXIS}' has {n}"
            )
        p = plan.ensure_padded(p)
        if x is not None:
            layout = plan.seq_layout(seq if seq is not None else x.shape[1])
            if x.shape[1] != layout.padded_len:
                raise ValueError(
                    f"sequence of {x.shape[1]} rows does not match the "
                    f"plan's padded ragged layout for seq={layout.seq} "
                    f"(tiles {list(layout.tiles)} pad to {layout.padded_len} "
                    f"rows); scatter it with plan.seq_layout(seq).scatter(x) "
                    f"and pass seq="
                )
            if layout.is_dense:
                layout = None
    return p, layout


def hmp_layer(p: Dict, x, mesh: Mesh, *, overlap: bool = False,
              plan: Optional[ExecPlan] = None, seq: Optional[int] = None):
    """Galaxy HMP layer.  x: (B, S, d) global.

    ``plan`` materializes an uneven planner assignment: reference-layout
    params are zero-padded per device (see ``ExecPlan.pad_layer_params``).
    A ragged SP plan (or a non-dividing length) additionally expects ``x``
    in the plan's padded ragged layout for the logical length ``seq``
    (``plan.seq_layout(seq).scatter(x)``); dense layouts take ``x`` as-is.
    """
    p, layout = _validate_plan(p, x, mesh, plan, seq=seq)
    backend = plan.compute_backend if plan is not None else "xla"
    fn = jax.shard_map(
        functools.partial(_hmp_layer_local, overlap=overlap, layout=layout,
                          plan=plan, backend=backend),
        mesh=mesh,
        in_specs=(layer_param_specs(), P(None, AXIS, None)),
        out_specs=P(None, AXIS, None),
        check_vma=backend == "xla",  # pallas_call has no replication rule
    )
    return fn(p, x)


# --- multi-layer serving path: prefill + single-token decode ------------------

def make_kv_cache(batch: int, cache_len: int, num_layers: int, mesh: Mesh,
                  plan: ExecPlan, dtype=jnp.float32) -> List[Dict]:
    """Head-sharded KV cache for a stack of HMP layers.

    Each layer holds k/v of global shape (B, cache_len, padded_heads, hd);
    the head axis carries the plan's padded layout, so cache shards line up
    with the weight shards and padded head slots stay zero forever.  The
    sequence axis is unsharded — cache_len only needs to fit the (padded)
    prefill length plus decode steps.
    """
    shape = (batch, cache_len, plan.padded_heads, plan.head_dim)
    sharding = NamedSharding(mesh, CACHE_SPEC)
    return [
        {"k": jax.device_put(jnp.zeros(shape, dtype), sharding),
         "v": jax.device_put(jnp.zeros(shape, dtype), sharding)}
        for _ in range(num_layers)
    ]


def _prefill_layer_local(p, x_loc, ck, cv, *, overlap: bool,
                         layout: Optional[SeqLayout] = None,
                         plan: Optional[ExecPlan] = None,
                         backend: str = "xla"):
    y_loc, k, v = _hmp_layer_local(p, x_loc, overlap=overlap, return_kv=True,
                                   layout=layout, plan=plan, backend=backend)
    if layout is not None:
        # ragged layout: cache rows are *absolute* positions — gather the
        # valid rows out of the padded order before writing, so decode's
        # position-indexed reads line up
        k, v = k[:, layout.rows], v[:, layout.rows]
    ck = jax.lax.dynamic_update_slice(ck, k, (0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v, (0, 0, 0, 0))
    return y_loc, ck, cv


def hmp_prefill(layers: Sequence[Dict], x, mesh: Mesh, cache: List[Dict],
                *, plan: ExecPlan, overlap: bool = False,
                seq: Optional[int] = None, block_row=None, offset=None):
    """Run a stack of HMP layers over a prompt, filling the KV cache.

    One keyword-normalized prefill entry point; the orthogonal knobs are

    * ``seq=``     — logical prompt length under a ragged layout (``x`` is
      then ``plan.seq_layout(seq).scatter`` of the prompt); dense layouts
      pass ``x`` as-is.
    * cache kind   — ``cache`` is the dense per-layer k/v list from
      ``make_kv_cache`` by default; passing ``block_row=`` (this request's
      physical page ids, ``(pages_per_slot,)``) makes it the paged pool
      from ``make_paged_kv_cache`` and K/V scatter straight into pages
      (batch must be 1).
    * ``offset=``  — chunked / suffix-only prefill (paged only): ``x`` is
      one chunk starting at absolute position ``offset``; K/V land at
      [offset, offset + seq) and the chunk attends back to every
      already-written position below ``offset``.  A traced int32 scalar is
      fine — one compiled program per chunk shape.

    x: (B, S, d).  K/V land in the cache at absolute positions either way.
    Returns (y, cache) with y in the same layout as x.
    """
    if block_row is None:
        if offset is not None:
            raise ValueError(
                "offset= (chunked prefill) needs a paged cache; pass the "
                "request's block_row= as well"
            )
        return _prefill_dense(layers, x, mesh, cache, plan=plan,
                              overlap=overlap, seq=seq)
    return _prefill_paged(layers, x, mesh, cache, block_row, plan=plan,
                          overlap=overlap, seq=seq, offset=offset)


def _prefill_dense(layers: Sequence[Dict], x, mesh: Mesh, cache: List[Dict],
                   *, plan: ExecPlan, overlap: bool, seq: Optional[int]):
    validated = [_validate_plan(p, x, mesh, plan, seq=seq) for p in layers]
    layers = [p for p, _ in validated]
    layout = validated[0][1] if validated else None
    backend = plan.compute_backend
    fn = jax.shard_map(
        functools.partial(_prefill_layer_local, overlap=overlap, layout=layout,
                          plan=plan, backend=backend),
        mesh=mesh,
        in_specs=(layer_param_specs(), P(None, AXIS, None), CACHE_SPEC, CACHE_SPEC),
        out_specs=(P(None, AXIS, None), CACHE_SPEC, CACHE_SPEC),
        check_vma=backend == "xla",
    )
    new_cache = []
    for p, c in zip(layers, cache):
        x, ck, cv = fn(p, x, c["k"], c["v"])
        new_cache.append({"k": ck, "v": cv})
    return x, new_cache


def _decode_mlp_tail(p, x, g, compute: Optional[_PallasCompute] = None):
    """Shared tail of the single-token TP step: attention output -> residual
    LN -> TP MLP (psum exit) -> residual LN.  ``compute`` routes the MLP
    GEMMs through the valid-length kernels (pad column blocks skipped)."""
    x = _ln(x + g, p["ln1_s"], p["ln1_b"])
    if compute is not None:
        h = jax.nn.gelu(compute.w1_gemm(x, p["w1"]))
        f = jax.lax.psum(compute.w2_gemm(h, p["w2"]), AXIS)
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w1"]))
        f = jax.lax.psum(jnp.einsum("bsf,fd->bsd", h, p["w2"]), AXIS)
    return _ln(x + f, p["ln2_s"], p["ln2_b"])


def _decode_qkv(p, x, compute: Optional[_PallasCompute]):
    """(B, S, d) -> q, k, v (B, S, h_loc, hd) through the backend (the
    fused-QKV projection shared by decode and the megatron baseline)."""
    h_loc, hd = p["wq"].shape[1], p["wq"].shape[2]
    if compute is None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        return q, k_new, v_new
    d_model = x.shape[-1]
    wqkv = jnp.concatenate(
        [p["wq"].reshape(d_model, -1), p["wk"].reshape(d_model, -1),
         p["wv"].reshape(d_model, -1)], axis=1)
    qkv = compute.qkv_gemm(x, wqkv)
    q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
    shape = (*x.shape[:2], h_loc, hd)
    return q.reshape(shape), k_new.reshape(shape), v_new.reshape(shape)


def _decode_layer_local(p, x, ck, cv, index, *,
                        plan: Optional[ExecPlan] = None,
                        backend: str = "xla"):
    """Single-token TP step on one device.  x: (B, 1, d) replicated; the SP
    axis is degenerate at one token, so connective blocks run redundantly and
    each TP block exits through an AllReduce (psum) instead of the ring.
    Writes this step's K/V into the local cache shard *before* attending, so
    position ``index`` is always valid.  index: (B,) per-slot positions —
    slots in a wave may sit at different depths (mixed-length prompts).

    With the pallas backend the projections shed pad head/column blocks;
    the attention core itself stays XLA (a cache gather + softmax, not an
    MXU-bound GEMM — pad head slots are zero in cache and query alike)."""
    d_model = x.shape[-1]
    b = x.shape[0]
    h_loc, hd = p["wq"].shape[1], p["wq"].shape[2]
    cache_len = ck.shape[1]
    compute = _make_compute(backend, plan, None, None)

    q, k_new, v_new = _decode_qkv(p, x, compute)
    rows = jnp.arange(b)
    ck = ck.at[rows, index].set(k_new[:, 0])
    cv = cv.at[rows, index].set(v_new[:, 0])

    scores = jnp.einsum("bqhd,bthd->bhqt", q, ck).astype(jnp.float32) / np.sqrt(hd)
    valid = jnp.arange(cache_len)[None, :] <= index[:, None]  # (B, T)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    attn = jnp.einsum("bhqt,bthd->bqhd", probs, cv).reshape(*x.shape[:2], h_loc * hd)
    if compute is not None:
        g = jax.lax.psum(compute.wo_gemm(attn, p["wo"].reshape(-1, d_model)), AXIS)
    else:
        g = jax.lax.psum(attn @ p["wo"].reshape(-1, d_model), AXIS)
    return _decode_mlp_tail(p, x, g, compute), ck, cv


def hmp_decode(layers: Sequence[Dict], x, mesh: Mesh, cache: List[Dict],
               index, *, plan: ExecPlan, block_table=None):
    """One decode step for a stack of HMP layers against the KV cache.

    The unified decode entry point: against the dense cache (default) x is
    a (B, 1, d) current-token embedding (replicated) and ``index`` a scalar
    int32 or (B,) vector of absolute positions (per-slot depths for
    mixed-length waves).  Passing ``block_table=`` ((S, W) int32 physical
    page per (slot, logical page)) makes ``cache`` the paged pool for a
    continuous-batching slot batch: x is (S, 1, d) and ``index`` the (S,)
    per-slot write positions.  Returns (y, cache) with y replicated.
    """
    if block_table is not None:
        return _decode_paged(layers, x, mesh, cache, block_table, index,
                             plan=plan)
    return _decode_dense(layers, x, mesh, cache, index, plan=plan)


def _decode_dense(layers: Sequence[Dict], x, mesh: Mesh, cache: List[Dict],
                  index, *, plan: ExecPlan):
    layers = [_validate_plan(p, None, mesh, plan)[0] for p in layers]
    backend = plan.compute_backend
    fn = jax.shard_map(
        functools.partial(_decode_layer_local, plan=plan, backend=backend),
        mesh=mesh,
        in_specs=(layer_param_specs(), P(), CACHE_SPEC, CACHE_SPEC, P()),
        out_specs=(P(), CACHE_SPEC, CACHE_SPEC),
        check_vma=backend == "xla",
    )
    index = jnp.asarray(index, jnp.int32)
    if index.ndim == 0:
        index = jnp.broadcast_to(index, (x.shape[0],))
    new_cache = []
    for p, c in zip(layers, cache):
        x, ck, cv = fn(p, x, c["k"], c["v"], index)
        new_cache.append({"k": ck, "v": cv})
    return x, new_cache


# --- paged serving path: pool pages + block tables ----------------------------

# pool pages are (num_pages, page_size, heads * head_dim): one row of lanes
# per position, heads-major, so it is stored unpadded and the programs'
# per-position scatter and page gather run in the layout it is stored in.
# The lane axis is sharded: under the plan's padded head layout each
# device's contiguous block of lanes is exactly its head shard, so page
# shards line up with the weight shards under any ExecPlan
PAGED_CACHE_SPEC = P(None, None, AXIS)


def make_paged_kv_cache(num_pages: int, page_size: int, num_layers: int,
                        mesh: Mesh, plan: ExecPlan,
                        dtype=jnp.float32) -> List[Dict]:
    """Head-sharded paged KV pool storage for a stack of HMP layers.

    Each layer holds k/v pages of global shape (num_pages, page_size,
    padded_heads * hd); the lane axis is the plan's padded head layout
    flattened heads-major, so a device's lane shard is its head shard and a
    slot's gathered pages reshape to ``(T, h_loc, hd)`` for attention.  The
    programs that write and read the pool use this shape directly: no step
    relayouts a whole layer's pool.  Page 0 is the null page
    (``serving/kvpool.py``): idle-slot writes land there and masked reads
    never see it.
    """
    shape = (num_pages, page_size, plan.padded_heads * plan.head_dim)
    sharding = NamedSharding(mesh, PAGED_CACHE_SPEC)
    return [
        {"k": jax.device_put(jnp.zeros(shape, dtype), sharding),
         "v": jax.device_put(jnp.zeros(shape, dtype), sharding)}
        for _ in range(num_layers)
    ]


def _paged_write(pk, pv, phys, within, k, v):
    """Scatter K/V rows (N, h_loc, hd) into pool rows ``(phys, within)``,
    each flattened to the pool's ``h_loc * hd`` lanes."""
    n = k.shape[0]
    return (pk.at[phys, within].set(k.reshape(n, -1)),
            pv.at[phys, within].set(v.reshape(n, -1)))


def _prefill_paged_layer_local(p, x_loc, pk, pv, phys, within, *, overlap,
                               layout: Optional[SeqLayout] = None,
                               plan: Optional[ExecPlan] = None,
                               backend: str = "xla"):
    """Prefill one layer and scatter its K/V head shards straight into pool
    pages.  phys/within: (S,) physical page and in-page slot per *absolute*
    position; under a ragged layout the valid rows are gathered out of the
    padded order first, so pad rows never touch the pool."""
    y_loc, k, v = _hmp_layer_local(p, x_loc, overlap=overlap, return_kv=True,
                                   layout=layout, plan=plan, backend=backend)
    if layout is not None:
        k, v = k[:, layout.rows], v[:, layout.rows]
    pk, pv = _paged_write(pk, pv, phys, within, k[0], v[0])
    return y_loc, pk, pv


def _prefill_chunk_layer_local(p, x_loc, pk, pv, phys, within, block_row,
                               offset, *, overlap,
                               layout: Optional[SeqLayout] = None,
                               plan: Optional[ExecPlan] = None,
                               backend: str = "xla"):
    """Chunked-prefill step for one layer: gather the request's pages as
    attention context (positions below ``offset`` — shared prefix pages and
    earlier chunks — are valid; later rows are masked in ``_ctx_attention``),
    run the chunk, then scatter its K/V head shards into the pages at
    absolute positions.  The gather happens *before* the scatter, so the
    chunk's own keys enter attention exactly once (from the fresh K/V)."""
    page_size = pk.shape[1]
    w = block_row.shape[0]
    h_loc, hd = p["wq"].shape[1], p["wq"].shape[2]
    ctx_k = pk[block_row].reshape(w * page_size, h_loc, hd)
    ctx_v = pv[block_row].reshape(w * page_size, h_loc, hd)
    y_loc, k, v = _hmp_layer_local(p, x_loc, overlap=overlap, return_kv=True,
                                   layout=layout, plan=plan, backend=backend,
                                   ctx=(ctx_k, ctx_v, offset))
    if layout is not None:
        k, v = k[:, layout.rows], v[:, layout.rows]
    pk, pv = _paged_write(pk, pv, phys, within, k[0], v[0])
    return y_loc, pk, pv


def _prefill_paged(layers: Sequence[Dict], x, mesh: Mesh,
                   pages: List[Dict], block_row, *, plan: ExecPlan,
                   overlap: bool, seq: Optional[int], offset):
    """Paged-pool prefill (see ``hmp_prefill``): x is (1, S, d) — the
    (bucket-padded) prompt for a dense layout, or the plan's padded ragged
    layout of a ``seq``-row prompt.  Bucket-padding positions beyond the
    real prompt write zero-token KV that decode overwrites before reading.
    K/V scatter into the block row's pages at absolute positions; with
    ``offset`` the chunk additionally gathers the block row as attention
    context (see ``_ctx_attention``).  Returns (y, pages)."""
    if x.shape[0] != 1:
        raise ValueError("paged prefill is per-request: batch must be 1")
    validated = [_validate_plan(p, x, mesh, plan, seq=seq) for p in layers]
    layers = [p for p, _ in validated]
    layout = validated[0][1] if validated else None
    s = x.shape[1] if layout is None else layout.seq
    page_size = pages[0]["k"].shape[1]
    if s > block_row.shape[0] * page_size:
        raise ValueError(
            f"prompt of {s} positions exceeds the block row "
            f"({block_row.shape[0]} pages x {page_size})"
        )
    backend = plan.compute_backend
    if offset is None:
        pos = jnp.arange(s)
        body = functools.partial(_prefill_paged_layer_local, overlap=overlap,
                                 layout=layout, plan=plan, backend=backend)
        extra_specs = ()
        extras = ()
    else:
        offset = jnp.asarray(offset, jnp.int32)
        pos = offset + jnp.arange(s)
        body = functools.partial(_prefill_chunk_layer_local, overlap=overlap,
                                 layout=layout, plan=plan, backend=backend)
        extra_specs = (P(), P())
        extras = (jnp.asarray(block_row, jnp.int32), offset)
    phys = block_row[pos // page_size].astype(jnp.int32)
    within = (pos % page_size).astype(jnp.int32)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(layer_param_specs(), P(None, AXIS, None),
                  PAGED_CACHE_SPEC, PAGED_CACHE_SPEC, P(), P(), *extra_specs),
        out_specs=(P(None, AXIS, None), PAGED_CACHE_SPEC, PAGED_CACHE_SPEC),
        check_vma=backend == "xla",
    )
    new_pages = []
    for p, c in zip(layers, pages):
        x, pk, pv = fn(p, x, c["k"], c["v"], phys, within, *extras)
        new_pages.append({"k": pk, "v": pv})
    return x, new_pages


def _paged_kv_gather(pool, block_table, head_ok):
    """Block-table gather whose pad head lanes read the null page.

    pool: (P, page, H*hd); block_table: (S, W); head_ok: (H,) bool — which
    padded head slots hold this device's real heads.  A valid head's lanes
    come from the slot's pages, a pad head's from the null page (page 0),
    whose pad-head lanes are zero forever (initialized zero; idle-slot
    writes put the projection of zero weights there), exactly what the
    whole-page gather reads out of real pages' pad lanes — so the result is
    bitwise-identical, and whatever a live page's pad lanes hold never
    reaches attention.  Returns (S, W*page, H, hd)."""
    s, w = block_table.shape
    h = head_ok.shape[0]
    lane_ok = jnp.repeat(head_ok, pool.shape[2] // h)
    out = jnp.where(lane_ok, pool[block_table], pool[0])
    return out.reshape(s, w * pool.shape[1], h, -1)


def _decode_paged_layer_local(p, x, pk, pv, block_table, positions, *,
                              plan: Optional[ExecPlan] = None,
                              backend: str = "xla"):
    """Paged single-token TP step on one device.  x: (S, 1, d) replicated
    slot batch; block_table: (S, W) physical page per (slot, logical page);
    positions: (S,) absolute position each slot writes this step.

    Scatters the new K/V entry (flattened to the pool's ``h_loc * hd``
    lanes) into its page, then gathers each slot's pages into a
    (S, W*page_size, h_loc, hd) view via the block table and attends
    under the per-slot length mask.  Idle slots carry all-null block rows:
    their write lands in the null page and every null read is masked.
    Backend routing mirrors ``_decode_layer_local``: projections shed pad
    blocks, the gather-attention core stays XLA."""
    d_model = x.shape[-1]
    h_loc, hd = p["wq"].shape[1], p["wq"].shape[2]
    page_size = pk.shape[1]
    w = block_table.shape[1]
    compute = _make_compute(backend, plan, None, None)

    q, k_new, v_new = _decode_qkv(p, x, compute)

    rows = jnp.arange(x.shape[0])
    phys = block_table[rows, positions // page_size]
    within = positions % page_size
    pk, pv = _paged_write(pk, pv, phys, within, k_new[:, 0], v_new[:, 0])

    # gather this slot's logical context: (S, W, page, h*hd) -> (S, T, h, hd)
    if plan is not None and len(set(plan.heads)) > 1:
        # uneven heads: pad heads' lanes come from the (zero) null page,
        # bitwise-equal to the whole-page gather.  Even plans keep the plain gather (and its
        # exact XLA graph): every slot is valid there.
        idx = jax.lax.axis_index(AXIS)
        head_ok = jnp.arange(h_loc) < jnp.asarray(plan.heads, jnp.int32)[idx]
        ks = _paged_kv_gather(pk, block_table, head_ok)
        vs = _paged_kv_gather(pv, block_table, head_ok)
    else:
        ks = pk[block_table].reshape(x.shape[0], w * page_size, h_loc, hd)
        vs = pv[block_table].reshape(x.shape[0], w * page_size, h_loc, hd)

    scores = jnp.einsum("bqhd,bthd->bhqt", q, ks).astype(jnp.float32) / np.sqrt(hd)
    valid = jnp.arange(w * page_size)[None, :] <= positions[:, None]  # (S, T)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(vs.dtype)
    attn = jnp.einsum("bhqt,bthd->bqhd", probs, vs).reshape(*x.shape[:2], h_loc * hd)
    if compute is not None:
        g = jax.lax.psum(compute.wo_gemm(attn, p["wo"].reshape(-1, d_model)), AXIS)
    else:
        g = jax.lax.psum(attn @ p["wo"].reshape(-1, d_model), AXIS)
    return _decode_mlp_tail(p, x, g, compute), pk, pv


def _decode_paged(layers: Sequence[Dict], x, mesh: Mesh,
                  pages: List[Dict], block_table, positions, *,
                  plan: ExecPlan):
    """Paged slot-batch decode step (see ``hmp_decode``): x is (S, 1, d)
    replicated; block_table (S, W) int32; positions (S,) int32 per-slot
    absolute positions.  Returns (y, pages) with y replicated."""
    layers = [_validate_plan(p, None, mesh, plan)[0] for p in layers]
    backend = plan.compute_backend
    fn = jax.shard_map(
        functools.partial(_decode_paged_layer_local, plan=plan, backend=backend),
        mesh=mesh,
        in_specs=(layer_param_specs(), P(), PAGED_CACHE_SPEC, PAGED_CACHE_SPEC,
                  P(), P()),
        out_specs=(P(), PAGED_CACHE_SPEC, PAGED_CACHE_SPEC),
        check_vma=backend == "xla",
    )
    block_table = jnp.asarray(block_table, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    new_pages = []
    for p, c in zip(layers, pages):
        x, pk, pv = fn(p, x, c["k"], c["v"], block_table, positions)
        new_pages.append({"k": pk, "v": pv})
    return x, new_pages


# --- Megatron-LM TP baseline -----------------------------------------------

def _megatron_layer_local(p, x, *, plan: Optional[ExecPlan] = None,
                          backend: str = "xla"):
    """x replicated; AllReduce after each block; connective computed
    redundantly on every device (the waste HMP eliminates).  The pallas
    backend sheds pad head/column blocks here too (x is the full dense
    sequence, so only the unit axes are ragged)."""
    h_loc, hd = p["wq"].shape[1], p["wq"].shape[2]
    d_model = x.shape[-1]
    compute = _make_compute(backend, plan, None, x.shape[1])
    q, k, v = _decode_qkv(p, x, compute)
    if compute is not None:
        attn = compute.attention(q, k, v)
        g = compute.wo_gemm(attn.reshape(*x.shape[:2], h_loc * hd),
                            p["wo"].reshape(-1, d_model))
    else:
        attn = _attention(q, k, v)
        g = jnp.einsum("bshk,hkd->bsd", attn, p["wo"])
    g = jax.lax.psum(g, AXIS)  # AllReduce #1
    x = _ln(x + g, p["ln1_s"], p["ln1_b"])  # redundant on all devices
    if compute is not None:
        h = jax.nn.gelu(compute.w1_gemm(x, p["w1"]))
        f = compute.w2_gemm(h, p["w2"])
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w1"]))
        f = jnp.einsum("bsf,fd->bsd", h, p["w2"])
    f = jax.lax.psum(f, AXIS)  # AllReduce #2
    x = _ln(x + f, p["ln2_s"], p["ln2_b"])
    return x


def megatron_layer(p: Dict, x, mesh: Mesh, *, plan: Optional[ExecPlan] = None):
    p, _ = _validate_plan(p, None, mesh, plan)
    backend = plan.compute_backend if plan is not None else "xla"
    fn = jax.shard_map(
        functools.partial(_megatron_layer_local, plan=plan, backend=backend),
        mesh=mesh,
        in_specs=(layer_param_specs(), P()),
        out_specs=P(),
        check_vma=backend == "xla",
    )
    return fn(p, x)


# --- pure Sequence Parallelism baseline ---------------------------------------

def _sp_layer_local(p, x_loc):
    """x seq-sharded; weights fully replicated (the memory wall).  K/V need
    the whole sequence: 2 AllGathers per MHA block (paper §IV-A)."""
    q = jnp.einsum("bsd,dhk->bshk", x_loc, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x_loc, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_loc, p["wv"])
    k = jax.lax.all_gather(k, AXIS, axis=1, tiled=True)  # AllGather #1
    v = jax.lax.all_gather(v, AXIS, axis=1, tiled=True)  # AllGather #2
    # causal offset of the local query block
    idx = jax.lax.axis_index(AXIS)
    s_loc = q.shape[1]
    hd = q.shape[-1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / np.sqrt(hd)
    q_pos = idx * s_loc + jnp.arange(s_loc)
    k_pos = jnp.arange(k.shape[1])
    mask = k_pos[None, :] <= q_pos[:, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    attn = jnp.einsum("bhst,bthd->bshd", probs, v)
    g = jnp.einsum("bshk,hkd->bsd", attn, p["wo"])
    x_loc = _ln(x_loc + g, p["ln1_s"], p["ln1_b"])
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x_loc, p["w1"]))
    f = jnp.einsum("bsf,fd->bsd", h, p["w2"])
    x_loc = _ln(x_loc + f, p["ln2_s"], p["ln2_b"])
    return x_loc


def sp_layer(p: Dict, x, mesh: Mesh, *, plan: Optional[ExecPlan] = None):
    # SP replicates weights: an uneven TP plan does not apply
    fn = jax.shard_map(
        _sp_layer_local,
        mesh=mesh,
        in_specs=(layer_param_specs(sp=True), P(None, AXIS, None)),
        out_specs=P(None, AXIS, None),
    )
    return fn(p, x)


SCHEDULES = {
    "hmp": lambda p, x, mesh, **kw: hmp_layer(p, x, mesh, overlap=False, **kw),
    "hmp_ring": lambda p, x, mesh, **kw: hmp_layer(p, x, mesh, overlap=True, **kw),
    "megatron": lambda p, x, mesh, **kw: megatron_layer(p, x, mesh, **kw),
    "sp": lambda p, x, mesh, **kw: sp_layer(p, x, mesh, **kw),
}
