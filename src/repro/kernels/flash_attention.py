"""Blocked (flash) attention Pallas kernel for TPU.

Design for the TPU memory hierarchy (DESIGN.md §2): Q/K/V blocks are staged
HBM->VMEM by BlockSpecs with MXU-aligned tiles (block_q x head_dim and
block_k x head_dim, multiples of 128 where shapes allow); the kernel keeps
the running max / normalizer / accumulator in VMEM scratch across the
sequential k-block grid axis (TPU grids iterate the last axis innermost),
which is the standard online-softmax accumulation pattern.

Supports causal and sliding-window masks (RecurrentGemma local attention,
and the long_500k sliding-window variant) and GQA via the kv-head index
map (q head h reads kv head h // group).

:func:`ragged_flash_attention` is the ``compute_backend="pallas"`` variant
for the HMP hot loop: queries/keys live in an ``execplan.SeqLayout`` padded
ragged order (position per padded row, -1 for pad rows), a static
block-level skip map derived from those positions prunes (q-block, k-block)
pairs that are entirely pad or entirely acausal, and a per-device
``valid_heads`` scalar-prefetch operand skips padded head slots outright —
so executed attention FLOPs track the plan's assigned heads, not
``max(heads)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiled_gemm import LANE, SUBLANE, aligned_block

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, sk: int, sq: int, block_q: int,
            block_k: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (block_q, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    # positions (queries right-aligned against the key sequence)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + (sk - sq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    # fully-masked rows: keep accumulator stable
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(
    q, k, v, *, causal: bool = True, window: int = 0,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
):
    """q: (B,H,Sq,hd); k,v: (B,Hkv,Sk,hd). Returns (B,H,Sq,hd)."""
    b, h, sq, hd = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"attention ({sq} q x {sk} k) does not tile into blocks "
            f"(block_q={block_q}, block_k={block_k}); blocks must divide"
        )
    scale = 1.0 / (hd ** 0.5)

    grid = (b, h, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _kernel, causal=causal, window=window, sk=sk, sq=sq,
        block_q=block_q, block_k=block_k, scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# --- ragged (SeqLayout-aware) variant ----------------------------------------

def attention_block_map(positions, block_q: int, block_k: int) -> np.ndarray:
    """Static (nq, nk) skip map of a ragged causal attention.

    ``positions[r]`` is the real position padded row ``r`` holds (-1 for pad
    rows).  A (q-block, k-block) pair is live iff some valid key in the
    k-block is causally visible to some valid query in the q-block; for a
    dense ``arange`` layout this reduces to the standard causal block skip.
    Blocks need not divide the rows: the last block of each axis is cut
    short (its tail holds no position).  The layout is trace-time static
    (it comes from ``ExecPlan.seq_layout``), so the map is plain numpy and
    enters the kernel as a scalar-prefetch operand.
    """
    pos = np.asarray(positions, int)
    (s,) = pos.shape
    nq, nk = -(-s // block_q), -(-s // block_k)
    live = np.zeros((nq, nk), np.int32)
    for qi in range(nq):
        qp = pos[qi * block_q:(qi + 1) * block_q]
        qp = qp[qp >= 0]
        if not qp.size:
            continue
        for ki in range(nk):
            kp = pos[ki * block_k:(ki + 1) * block_k]
            kp = kp[kp >= 0]
            if kp.size and kp.min() <= qp.max():
                live[qi, ki] = 1
    return live


def _ragged_kernel(vh_ref, bm_ref, q_ref, k_ref, v_ref, pq_ref, pk_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, s: int,
                   block_k: int):
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip pad head slots (per-device scalar) and pruned block pairs
    live = (hi < vh_ref[0]) & (bm_ref[qi, ki] > 0)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)   # (block_q, hd)
        kk = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
        vv = v_ref[0, 0].astype(jnp.float32)
        if s % block_k:
            # the last key block reads past the end of v, where the buffer
            # may hold non-finite values that a zero probability would not
            # cancel
            krow = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, vv.shape, 0)
            vv = jnp.where(krow < s, vv, 0.0)

        sc = jnp.dot(q, kk.T, preferred_element_type=jnp.float32) * scale
        pq = pq_ref[...]  # (block_q, 1) query positions, -1 = pad
        pk = pk_ref[...]  # (1, block_k) key positions, -1 = pad
        mask = (pq >= 0) & (pk >= 0) & (pk <= pq)
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        # fully-masked rows keep the accumulator stable (exp guard)
        p = jnp.where(sc > NEG_INF / 2, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, vv, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        # rows with no live contribution (pad queries, pad heads) emit zero
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def ragged_flash_attention(
    q, k, v, *, positions, valid_heads=None,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
):
    """Causal flash attention over a padded ragged row order.

    q: (B,H,S,hd); k,v: (B,Hkv,S,hd); positions: (S,) static int row->real
    position (-1 = pad row).  ``valid_heads`` (traced scalar ok) marks the
    leading real head slots of this device's padded shard — padded heads
    and pruned (q, k) block pairs are skipped entirely, pad query rows come
    out exactly zero, and valid rows match ``flash_attention_ref`` over the
    compacted sequence.

    Blocks are (8, 128)-aligned or span the sequence
    (``tiled_gemm.aligned_block``); the positions are staged as a (S, 1)
    query column and a (1, S) key row, padded with -1 past the sequence,
    so the masked tail of the last block never attends or is attended.
    """
    b, h, s, hd = q.shape
    hkv = k.shape[1]
    g = h // hkv
    block_q = aligned_block(s, block_q, SUBLANE)
    # key positions ride the lane axis of their (1, block_k) block
    block_k = aligned_block(s, block_k, LANE)
    nq, nk = pl.cdiv(s, block_q), pl.cdiv(s, block_k)
    scale = 1.0 / (hd ** 0.5)

    pos = np.asarray(positions, np.int32)
    block_map = attention_block_map(pos, block_q, block_k)
    pos_q = np.full(nq * block_q, -1, np.int32)
    pos_q[:s] = pos
    pos_k = np.full(nk * block_k, -1, np.int32)
    pos_k[:s] = pos
    vh = jnp.asarray(h if valid_heads is None else valid_heads,
                     jnp.int32).reshape(1)

    kernel = functools.partial(_ragged_kernel, scale=scale, s=s,
                               block_k=block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # valid_heads, block skip map
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, ki, vh, bm: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, vh, bm: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, vh, bm: (bi, hi // g, ki, 0)),
            pl.BlockSpec((block_q, 1), lambda bi, hi, qi, ki, vh, bm: (qi, 0)),
            pl.BlockSpec((1, block_k), lambda bi, hi, qi, ki, vh, bm: (0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, ki, vh, bm: (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        interpret=interpret,
    )(vh, jnp.asarray(block_map), q, k, v,
      jnp.asarray(pos_q)[:, None], jnp.asarray(pos_k)[None, :])
