"""The DeepSeek-V3 block on the Galaxy serving path: latent attention (MLA)
and a dropless mixture of experts, as paged shard_map programs.

The second layer kind beside the paper's Fig. 2 layer (``core/hmp.py``).
A layer is an :class:`MLALayer` (its arrays and the static
hyperparameters no shape gives), the vocabulary a :class:`Vocab` (embedding,
final RMSNorm, untied head): ``serving/galaxy.GalaxyMLAExecutor`` (a
``GalaxyHMPExecutor``) serves a stack of ``MLALayer``\\ s through the same
paged entry points as the paper layer.

* **Attention** (pre-norm RMSNorm): the query is projected per head to
  ``qk_nope + qk_rope`` lanes (no query compression); the key/value path
  compresses each position to one latent, ``kv_lora_rank`` lanes after an
  RMSNorm, plus a ``qk_rope``-wide RoPE key shared by all heads (RoPE on
  interleaved pairs).  The pool holds that row, ``kv_lora_rank + qk_rope``
  lanes a position and layer padded to whole lane tiles, replicated over
  the model axis.  Prefill
  expands the latent to per-head keys and values (``wk_b`` / ``wv_b``);
  decode absorbs ``wk_b`` into the query and ``wv_b`` into the output and
  attends in the latent space, reading each position's row once for all
  heads.  Softmax scale ``(qk_nope + qk_rope) ** -0.5``.
* **Feed-forward**: a dense SwiGLU (layers that hold ``w_gate``), or a
  mixture of experts: a sigmoid router over every expert of the layer, run
  in float32 at ``highest`` precision; the top ``experts_per_token`` of
  score + ``router_bias`` are chosen; their raw scores, normalised and
  scaled by ``routed_scale``, weight the experts.  The layer holds a share
  of the routed experts (``held``: their global ids) and computes, with
  grouped GEMMs (``jax.lax.ragged_dot``) over the assignments sorted by
  expert, only the assignments of those it holds: no capacity, no token
  dropped, so a row's result never depends on the rest of the batch.  A
  token none of whose experts is held gets the shared experts only.  The
  shared experts (one SwiGLU of their summed width) are added once.

Sharding: heads and held experts lie on the model axis; the latent pool,
norms, the router, the dense and shared SwiGLUs and the vocabulary are
replicated; the attention output and the routed experts' sum leave through
an AllReduce.  The executor runs this block on a one-device plan only (a
four-chip plan has no cell to judge it yet).

Named scopes, for the device trace: ``mla.latent`` (projections into and
out of the latent space, the pool write), ``mla.attention`` (the attention
core over the pool or the expanded keys), ``moe.router``, ``moe.experts``,
``moe.shared``.

Each program also returns the chosen expert ids of every MoE layer,
``(moe_layers, tokens, experts_per_token)``, and the number of assignments
each held expert computed, ``(moe_layers, held)``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "model"
#: query rows attended per block in prefill: bounds the live score buffer
#: at (heads, QUERY_BLOCK, keys)
QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Block:
    """Static hyperparameters of a layer that its shapes do not give."""

    experts_per_token: int
    routed_scale: float
    rope_theta: float
    eps: float


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MLALayer:
    """One DeepSeek-V3 layer: ``p`` its arrays (keys in ``PARAM_SPECS``),
    dense when it holds ``w_gate``, a mixture of experts when it holds
    ``router``."""

    p: Dict[str, jax.Array]
    block: Block = dataclasses.field(metadata=dict(static=True))

    @property
    def is_moe(self) -> bool:
        return "router" in self.p


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Vocab:
    """Embedding table (V, d), final RMSNorm scale (d,), untied head (d, V)."""

    table: jax.Array
    norm: jax.Array
    head: jax.Array


PARAM_SPECS = {
    "attn_norm": P(), "wq": P(None, AXIS, None), "wkv_a": P(),
    "kv_norm": P(), "wk_b": P(None, AXIS, None), "wv_b": P(None, AXIS, None),
    "wo": P(AXIS, None, None), "ffn_norm": P(),
    "w_gate": P(), "w_up": P(), "w_down": P(),
    "router": P(), "router_bias": P(), "held": P(AXIS),
    "e_gate": P(AXIS, None, None), "e_up": P(AXIS, None, None),
    "e_down": P(AXIS, None, None),
    "s_gate": P(), "s_up": P(), "s_down": P(),
}


def layer_specs(layer: MLALayer) -> MLALayer:
    return MLALayer({k: PARAM_SPECS[k] for k in layer.p}, layer.block)


def layer_shardings(layer: MLALayer, mesh: Mesh) -> MLALayer:
    return MLALayer({k: NamedSharding(mesh, PARAM_SPECS[k]) for k in layer.p},
                    layer.block)


def block_of(cfg) -> Block:
    return Block(cfg.experts_per_token, float(cfg.routed_scale),
                 float(cfg.rope_theta), float(cfg.eps))


def from_arrays(cfg, layers: Sequence[Dict], vocab: Dict
                ) -> Tuple[List[MLALayer], Vocab]:
    """Program pytrees of arrays in the reference layout: ``layers`` dicts
    keyed as ``PARAM_SPECS``, ``vocab`` a dict of ``table``, ``norm``,
    ``head``."""
    b = block_of(cfg)
    return ([MLALayer(dict(p), b) for p in layers],
            Vocab(vocab["table"], vocab["norm"], vocab["head"]))


def init_params(cfg, key, dtype=jnp.float32
                ) -> Tuple[List[MLALayer], Vocab]:
    """Seeded weights of an ``MLAMoEConfig`` stack (scaled so activations
    stay near unit size; norm scales drawn around 1, router bias drawn, so
    a skipped norm or bias shows).  MoE layers hold experts
    ``0 .. experts_held - 1``."""
    d, h = cfg.d_model, cfg.num_heads
    n, r, vd, rank = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, \
        cfg.kv_lora_rank
    e, f, fs = cfg.experts_held, cfg.d_expert, cfg.d_expert * cfg.num_shared

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def scale(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    layers = []
    for i in range(cfg.num_layers):
        ks = iter(jax.random.split(jax.random.fold_in(key, i), 16))
        p = {"attn_norm": scale(next(ks), (d,)),
             "wq": normal(next(ks), (d, h, n + r), d),
             "wkv_a": normal(next(ks), (d, rank + r), d),
             "kv_norm": scale(next(ks), (rank,)),
             "wk_b": normal(next(ks), (rank, h, n), rank),
             "wv_b": normal(next(ks), (rank, h, vd), rank),
             "wo": normal(next(ks), (h, vd, d), h * vd),
             "ffn_norm": scale(next(ks), (d,))}
        if i < cfg.first_dense:
            p.update(w_gate=normal(next(ks), (d, cfg.d_ff), d),
                     w_up=normal(next(ks), (d, cfg.d_ff), d),
                     w_down=normal(next(ks), (cfg.d_ff, d), cfg.d_ff))
        else:
            p.update(router=normal(next(ks), (d, cfg.num_experts), d)
                     .astype(jnp.float32),
                     router_bias=0.1 * jax.random.normal(
                         next(ks), (cfg.num_experts,), jnp.float32),
                     held=jnp.arange(e, dtype=jnp.int32),
                     e_gate=normal(next(ks), (e, d, f), d),
                     e_up=normal(next(ks), (e, d, f), d),
                     e_down=normal(next(ks), (e, f, d), f),
                     s_gate=normal(next(ks), (d, fs), d),
                     s_up=normal(next(ks), (d, fs), d),
                     s_down=normal(next(ks), (fs, d), fs))
        layers.append(p)
    kv = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    vocab = {"table": normal(kv[0], (cfg.vocab_size, d), 1.0) * 0.5,
             "norm": scale(kv[1], (d,)),
             "head": normal(kv[2], (d, cfg.vocab_size), d)}
    return from_arrays(cfg, layers, vocab)


def moe_layers(layers: Sequence[MLALayer]) -> int:
    return sum(l.is_moe for l in layers)


def experts_held(layers: Sequence[MLALayer]) -> int:
    return next((l.p["held"].shape[0] for l in layers if l.is_moe), 0)


# --- pool ---------------------------------------------------------------------


#: pool rows are padded to a whole number of 128-lane tiles: a row of
#: ``kv_lora_rank + qk_rope`` = 576 lanes would make XLA store the pool
#: position-minor, and every step would then relayout each layer's whole
#: pool for the row scatter and back.  The TPU pads a row to its lane tile
#: in memory either way, so the pad lanes cost no memory or bandwidth.
LANE = 128


def pool_lanes(layer: MLALayer) -> int:
    return -(-layer.p["wkv_a"].shape[1] // LANE) * LANE


def make_latent_pool(num_pages: int, page_size: int,
                     layers: Sequence[MLALayer], mesh: Mesh,
                     dtype=jnp.float32) -> List[Dict]:
    """The paged latent pool: per layer ``{"c": (num_pages, page_size,
    lanes)}``, each row the normalised latent, the RoPE key and zero pad
    lanes (``pool_lanes``), replicated over the model axis.  Page 0 is the
    null page (``serving/kvpool.py``)."""
    sharding = NamedSharding(mesh, P())
    return [{"c": jax.device_put(jnp.zeros(
        (num_pages, page_size, pool_lanes(l)), dtype), sharding)}
        for l in layers]


# --- the layer's pieces (per device) -------------------------------------------


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * scale).astype(x.dtype)


def rope(x, pos, theta: float):
    """RoPE on interleaved pairs ``(x[2i], x[2i+1])`` at frequency
    ``theta ** (-2i / r)``.  x: (T, ..., r); pos: (T,)."""
    r = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, r, 2, dtype=np.float64) / r),
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x2 = x.reshape(*x.shape[:-1], r // 2, 2)
    a, b = x2[..., 0], x2[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape).astype(x.dtype)


def _rank(p) -> int:
    return p["kv_norm"].shape[0]


def _scale(p) -> float:
    return float(p["wq"].shape[-1]) ** -0.5


def _rope_dim(p) -> int:
    return p["wkv_a"].shape[1] - _rank(p)


def latent(p, xn, pos, b: Block, lanes: int):
    """(T, d) normed input -> (T, lanes) pool rows: the normalised latent,
    the RoPE key, zero pad lanes."""
    rank = _rank(p)
    ckv = xn @ p["wkv_a"]
    rows = jnp.concatenate([rms_norm(ckv[:, :rank], p["kv_norm"], b.eps),
                            rope(ckv[:, rank:], pos, b.rope_theta)], -1)
    return jnp.pad(rows, ((0, 0), (0, lanes - rows.shape[1])))


def query(p, xn, pos, b: Block):
    """(T, d) -> (T, h_loc, nope), (T, h_loc, rope)."""
    n = p["wk_b"].shape[-1]
    q = jnp.einsum("td,dhk->thk", xn, p["wq"])
    return q[..., :n], rope(q[..., n:], pos, b.rope_theta)


def expand(p, rows):
    """Pool rows (T, lanes) -> per-head keys (T, h_loc, nope + rope) and
    values (T, h_loc, v)."""
    rank = _rank(p)
    c, kpe = rows[:, :rank], rows[:, rank:rank + _rope_dim(p)]
    k = jnp.einsum("tc,chn->thn", c, p["wk_b"])
    v = jnp.einsum("tc,chv->thv", c, p["wv_b"])
    kpe = jnp.broadcast_to(kpe[:, None, :], (*k.shape[:2], kpe.shape[-1]))
    return jnp.concatenate([k, kpe], -1), v


def _query_block(s: int) -> int:
    if s <= QUERY_BLOCK:
        return s
    for blk in (QUERY_BLOCK, 256, 128, 64, 32, 16, 8):
        if s % blk == 0:
            return blk
    return s


def expanded_attention(q, k, v, q_pos, k_pos, k_ok, scale: float):
    """Causal attention of queries (S, h, dk) at ``q_pos`` over keys (T, h,
    dk) / values (T, h, dv) at ``k_pos`` where ``k_ok``, in blocks of query
    rows."""
    s, h = q.shape[:2]
    blk = _query_block(s)

    def one(args):
        qb, pb = args
        sc = jnp.einsum("shk,thk->hst", qb, k).astype(jnp.float32) * scale
        ok = k_ok[None, :] & (k_pos[None, :] <= pb[:, None])
        sc = jnp.where(ok[None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        return jnp.einsum("hst,thv->shv", pr, v)

    out = lax.map(one, (q.reshape(s // blk, blk, h, -1),
                        q_pos.reshape(s // blk, blk)))
    return out.reshape(s, h, -1)


def absorbed_attention(p, q_nope, q_pe, ctx, valid):
    """Decode attention in the latent space: queries (S, h, nope) absorb
    ``wk_b``, so each head scores the pool rows ``ctx`` (S, T, lanes)
    directly (the query's pad lanes are zero); the weighted latent (S, h,
    rank) leaves through ``wv_b``.  valid: (S, T).  Returns (S, h, v)."""
    rank = _rank(p)
    with jax.named_scope("mla.latent"):
        qa = jnp.concatenate(
            [jnp.einsum("shn,chn->shc", q_nope, p["wk_b"]), q_pe], -1)
        qa = jnp.pad(qa, ((0, 0), (0, 0), (0, ctx.shape[-1] - qa.shape[-1])))
    with jax.named_scope("mla.attention"):
        sc = jnp.einsum("shl,stl->sht", qa, ctx).astype(jnp.float32) \
            * _scale(p)
        sc = jnp.where(valid[:, None, :], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1).astype(ctx.dtype)
        o = jnp.einsum("sht,stc->shc", pr, ctx[..., :rank])
    with jax.named_scope("mla.latent"):
        return jnp.einsum("shc,chv->shv", o, p["wv_b"])


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def route(p, xn, b: Block):
    """The router, in float32 at ``highest``: sigmoid scores over every
    expert, the top ``experts_per_token`` of score + bias, their raw scores
    normalised and scaled.  Returns ids (T, k) int32 and weights (T, k)."""
    logits = jnp.dot(xn.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                       b.experts_per_token)
    w = jnp.take_along_axis(scores, ids, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * b.routed_scale
    return ids.astype(jnp.int32), w


def routed_experts(p, xn, ids, w):
    """This device's held experts' part of the layer: the assignments whose
    expert it holds, sorted by expert, through grouped GEMMs; every other
    assignment is skipped.  Returns (T, d) and the assignments each held
    expert computed (held,)."""
    held = p["held"]
    e = held.shape[0]
    t, k = ids.shape
    hit = ids[..., None] == held
    here = hit.any(-1).reshape(-1)
    key = jnp.where(here, jnp.argmax(hit, -1).reshape(-1), e)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=e + 1)[:e].astype(jnp.int32)
    xs = xn[order // k]
    hid = jax.nn.silu(lax.ragged_dot(xs, p["e_gate"], sizes)) \
        * lax.ragged_dot(xs, p["e_up"], sizes)
    y = lax.ragged_dot(hid, p["e_down"], sizes)
    # back to (token, choice) order, each choice weighted, then summed: a
    # gather in place of a scatter-add with repeated rows
    y = y[jnp.argsort(order)]
    y = jnp.where(here[:, None], y * w.reshape(-1, 1).astype(y.dtype), 0)
    return y.reshape(t, k, -1).sum(1), sizes


def feed_forward(layer: MLALayer, x):
    """Pre-norm feed-forward with its residual.  Returns (x, ids, sizes),
    ids and sizes None for a dense layer."""
    p, b = layer.p, layer.block
    xn = rms_norm(x, p["ffn_norm"], b.eps)
    if not layer.is_moe:
        return x + swiglu(xn, p["w_gate"], p["w_up"], p["w_down"]), None, None
    with jax.named_scope("moe.router"):
        ids, w = route(p, xn, b)
    with jax.named_scope("moe.experts"):
        routed, sizes = routed_experts(p, xn, ids, w)
        routed = lax.psum(routed, AXIS)
    with jax.named_scope("moe.shared"):
        shared = swiglu(xn, p["s_gate"], p["s_up"], p["s_down"])
    return x + routed + shared, ids, sizes


def _routing(ids: List, sizes: List, t: int, k: int, e: int):
    if not ids:  # no MoE layer
        return jnp.zeros((0, t, k), jnp.int32), jnp.zeros((0, e), jnp.int32)
    return jnp.stack(ids), jnp.stack(sizes)


# --- the stack, per device -------------------------------------------------------


def _prefill_local(layers, x, pools, phys, within, pos, ctx_row, offset):
    """Prefill of one request's rows x (S, d) at absolute positions ``pos``:
    each layer writes its pool rows at (phys, within) and attends over its
    own expanded rows; with ``ctx_row`` (a chunk) also over the block row's
    rows written before ``offset``, gathered before the write."""
    k = layers[0].block.experts_per_token
    ids, sizes, out = [], [], []
    for layer, pool in zip(layers, pools):
        p, b = layer.p, layer.block
        xn = rms_norm(x, p["attn_norm"], b.eps)
        with jax.named_scope("mla.latent"):
            c = pool["c"]
            rows = latent(p, xn, pos, b, c.shape[-1])
            q_nope, q_pe = query(p, xn, pos, b)
            if ctx_row is not None:
                ctx = c[ctx_row].reshape(-1, c.shape[-1])
            c = c.at[phys, within].set(rows.astype(c.dtype))
        with jax.named_scope("mla.attention"):
            kk, vv = expand(p, rows)
            k_pos, k_ok = pos, jnp.ones(pos.shape, bool)
            if ctx_row is not None:
                kc, vc = expand(p, ctx.astype(rows.dtype))
                kk, vv = jnp.concatenate([kc, kk]), jnp.concatenate([vc, vv])
                t = jnp.arange(ctx.shape[0])
                k_pos = jnp.concatenate([t, pos])
                k_ok = jnp.concatenate([t < offset, k_ok])
            attn = expanded_attention(jnp.concatenate([q_nope, q_pe], -1),
                                      kk, vv, pos, k_pos, k_ok, _scale(p))
        with jax.named_scope("mla.latent"):
            g = lax.psum(jnp.einsum("thv,hvd->td", attn, p["wo"]), AXIS)
        x, i, s = feed_forward(layer, x + g)
        out.append({"c": c})
        if i is not None:
            ids.append(i)
            sizes.append(s)
    return (x, out, *_routing(ids, sizes, x.shape[0], k, experts_held(layers)))


def _decode_local(layers, x, pools, block_table, positions):
    """One decode step of the slot batch x (S, d): each layer writes the
    slot's pool row at its position, gathers the slot's block row and
    attends in the latent space under the per-slot length mask."""
    s, w = block_table.shape
    page = pools[0]["c"].shape[1]
    phys = block_table[jnp.arange(s), positions // page]
    within = positions % page
    valid = jnp.arange(w * page)[None, :] <= positions[:, None]
    k = layers[0].block.experts_per_token
    ids, sizes, out = [], [], []
    for layer, pool in zip(layers, pools):
        p, b = layer.p, layer.block
        xn = rms_norm(x, p["attn_norm"], b.eps)
        with jax.named_scope("mla.latent"):
            c = pool["c"]
            rows = latent(p, xn, positions, b, c.shape[-1])
            q_nope, q_pe = query(p, xn, positions, b)
            c = c.at[phys, within].set(rows.astype(c.dtype))
        with jax.named_scope("mla.attention"):
            ctx = c[block_table].reshape(s, w * page, c.shape[-1])
        attn = absorbed_attention(p, q_nope, q_pe, ctx, valid)
        with jax.named_scope("mla.latent"):
            g = lax.psum(jnp.einsum("shv,hvd->sd", attn, p["wo"]), AXIS)
        x, i, sz = feed_forward(layer, x + g)
        out.append({"c": c})
        if i is not None:
            ids.append(i)
            sizes.append(sz)
    return (x, out, *_routing(ids, sizes, s, k, experts_held(layers)))


# --- entry points -------------------------------------------------------------------


_POOL = {"c": P()}


def prefill(layers: Sequence[MLALayer], x, mesh: Mesh, pools: List[Dict],
            block_row, *, offset=None):
    """Prefill one request (x: (S, d) embedded, bucket-padded) into its
    pool pages ``block_row``; with ``offset`` a chunk at absolute positions
    [offset, offset + S) that attends back to the rows written before it.
    Returns (y (S, d), pools, ids (moe_layers, S, k), sizes (moe_layers,
    held))."""
    s = x.shape[0]
    page = pools[0]["c"].shape[1]
    if s > block_row.shape[0] * page:
        raise ValueError(f"prompt of {s} positions exceeds the block row "
                         f"({block_row.shape[0]} pages x {page})")
    block_row = jnp.asarray(block_row, jnp.int32)
    chunk = offset is not None
    offset = jnp.asarray(0 if offset is None else offset, jnp.int32)
    pos = offset + jnp.arange(s, dtype=jnp.int32)
    phys, within = block_row[pos // page], pos % page

    def body(layers, x, pools, phys, within, pos, row, off):
        return _prefill_local(layers, x, pools, phys, within, pos,
                              row if chunk else None, off)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=([layer_specs(l) for l in layers], P(), [_POOL] * len(pools),
                  P(), P(), P(), P(), P()),
        out_specs=(P(), [_POOL] * len(pools), P(), P(None, AXIS)))
    return fn(list(layers), x, pools, phys, within, pos, block_row, offset)


def decode(layers: Sequence[MLALayer], x, mesh: Mesh, pools: List[Dict],
           block_table, positions):
    """One decode step of the slot batch x (S, d) against the pool: block
    table (S, W), positions (S,).  Returns (y (S, d), pools, ids
    (moe_layers, S, k), sizes (moe_layers, held))."""
    fn = jax.shard_map(
        _decode_local, mesh=mesh,
        in_specs=([layer_specs(l) for l in layers], P(), [_POOL] * len(pools),
                  P(), P()),
        out_specs=(P(), [_POOL] * len(pools), P(), P(None, AXIS)))
    return fn(list(layers), x, pools, jnp.asarray(block_table, jnp.int32),
              jnp.asarray(positions, jnp.int32))


def head(vocab: Vocab, y, eps: float):
    """Final RMSNorm and the untied head: (..., d) -> (..., V)."""
    return rms_norm(y, vocab.norm, eps) @ vocab.head
