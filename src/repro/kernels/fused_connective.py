"""Fused connective-block Pallas kernel: dropout -> residual add -> layernorm.

The paper's motivation for SP on connective blocks is that these element-wise
ops are *memory-bandwidth* bound (§III-B-3): executed separately they make
3-4 passes over the activations.  This kernel fuses them into a single
HBM->VMEM->HBM pass over (block_s x d) tiles — one read of x / residual /
mask, one write — cutting connective-block traffic ~3x (see roofline notes).

Dropout consumes a precomputed keep-mask (generated with jax.random outside)
so the kernel is deterministic and bit-reproducible across schedules.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiled_gemm import SUBLANE


def _kernel(x_ref, res_ref, mask_ref, scale_ref, bias_ref, o_ref, *,
            rate: float, eps: float):
    x = x_ref[...].astype(jnp.float32)
    if rate > 0:
        x = x * mask_ref[...].astype(jnp.float32) / (1.0 - rate)
    y = x + res_ref[...].astype(jnp.float32)
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), axis=-1, keepdims=True)
    out = (y - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def fused_connective(
    x, res, keep_mask, scale, bias, *, rate: float = 0.0, eps: float = 1e-5,
    block_s: int = 256, interpret: bool = False,
):
    """x, res, keep_mask: (S, d); scale, bias: (d,).  One pass over HBM.

    ``block_s`` must be a multiple of 8 (the sublane tile) or cover all
    rows; rows are independent, so a last block that runs past the array
    needs no mask (its tail rows are dropped by the output store)."""
    s, d = x.shape
    block_s = min(block_s, s)
    if block_s != s and block_s % SUBLANE:
        raise ValueError(
            f"connective block_s={block_s} is neither a multiple of "
            f"{SUBLANE} nor all {s} rows"
        )
    grid = (pl.cdiv(s, block_s),)
    kernel = functools.partial(_kernel, rate=rate, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, d), lambda i: (i, 0)),
            pl.BlockSpec((block_s, d), lambda i: (i, 0)),
            pl.BlockSpec((block_s, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_s, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, d), x.dtype),
        interpret=interpret,
    )(x, res, keep_mask, scale, bias)
