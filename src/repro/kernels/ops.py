"""Public wrappers for the Pallas kernels.

Off the TPU (the CPU test runs) the kernels run with ``interpret=True``
(the Pallas interpreter executes the kernel body — functionally identical
to the TPU lowering).  On a TPU backend ``interpret`` is False and the same
calls compile to Mosaic; ``chip_smoke.py`` checks that the served programs
contain the Mosaic custom call.

Besides the standalone jit'd wrappers, this module is the dispatch point of
the ``ExecPlan.compute_backend`` knob: :func:`gemm` and
:func:`ragged_attention` are what the HMP executor (``core/hmp.py``) and the
ring primitives (``core/ring.py``) call per shard.  ``backend="xla"`` keeps
the padded dense einsum (the pad-and-mask correctness oracle);
``backend="pallas"`` routes through the valid-length kernels, whose grids
skip pad blocks so executed MXU work tracks each device's *assigned* units
instead of ``max(units)``.  These run inside jitted shard_map bodies, so
they are plain functions (no extra jit layer).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_attention import ragged_flash_attention as _ragged_flash
from repro.kernels.fused_connective import fused_connective as _connective
from repro.kernels.tiled_gemm import tiled_gemm as _gemm
from repro.kernels.tiled_gemm import tiled_gemm_valid as _gemm_valid


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# --- compute-backend dispatch (ExecPlan.compute_backend) ----------------------

COMPUTE_BACKENDS = ("xla", "pallas")


def gemm(x, w, *, backend: str = "xla", valid_m=None, valid_n=None,
         valid_k=None, seg_n=None, block_m: int = 128, block_n: int = 128,
         block_k: int = 512):
    """(..., M, K) @ (K, N) through the selected compute backend.

    With ``valid_m`` each leading index of ``x`` is one M segment (one per
    batch row) with ``valid_m`` real leading rows; without it every row is
    real and the leading dims fold into one M axis (a decode batch is one
    GEMM).  ``valid_n`` names the real leading columns of each
    ``seg_n``-column segment of ``w`` (e.g. the q/k/v thirds of a fused QKV
    weight) and ``valid_k`` the real contraction prefix.  Valid counts may
    be traced scalars — they are per-device quantities inside shard_map.

    xla: a dense dot over the padded shapes with the valid counts applied
    as masks (every pad block still executes — the SPMD oracle), so both
    backends compute the identical function of the valid regions whatever
    the pad regions hold.  pallas: the valid-length tiled kernel, shedding
    whole pad blocks.
    """
    if backend not in COMPUTE_BACKENDS:
        raise ValueError(f"unknown compute backend {backend!r}; "
                         f"one of {COMPUTE_BACKENDS}")
    if backend == "xla":
        m, kk = x.shape[-2], x.shape[-1]
        n = w.shape[1]
        if valid_m is not None:
            rows = jnp.arange(m) < valid_m
            x = jnp.where(rows[:, None], x, 0)
        if valid_k is not None:
            cols = jnp.arange(kk) < valid_k
            x = jnp.where(cols[None, :], x, 0)
        out = jnp.einsum("...mk,kn->...mn", x, w)
        if valid_n is not None:
            seg = n if seg_n is None else seg_n
            keep = (jnp.arange(n) % seg) < valid_n
            out = jnp.where(keep, out, 0)
        return out
    if valid_m is None:
        x3 = x.reshape(1, -1, x.shape[-1])
    else:
        x3 = x.reshape(-1, *x.shape[-2:])
    out = _gemm_valid(
        x3, w, valid_m=valid_m, valid_n=valid_n, valid_k=valid_k,
        seg_n=seg_n, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=_default_interpret(),
    )
    return out.reshape(*x.shape[:-1], w.shape[1])


def ragged_attention(q, k, v, *, positions, valid_heads=None,
                     block_q: int = 128, block_k: int = 128):
    """Causal attention over a padded ragged row order, (B, S, H, hd)
    executor layout.  ``positions`` is the static ``SeqLayout.positions``
    map (-1 = pad row; ``arange`` for a dense layout) and ``valid_heads``
    this device's real head count (traced scalar ok).  Pad rows/heads come
    out exactly zero; always the pallas path (the xla equivalent is the
    caller's masked einsum)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _ragged_flash(
        qt, kt, vt, positions=positions, valid_heads=valid_heads,
        block_q=block_q, block_k=block_k, interpret=_default_interpret(),
    )
    return out.transpose(0, 2, 1, 3)


def connective(x, res, scale, bias, *, block_s: int = 256):
    """Fused residual-add + layernorm over (..., S, d) activations — the
    Galaxy connective block as one HBM pass (dropout disabled at
    inference).  Used by the pallas backend in place of the unfused
    residual + LN pair."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    res2 = res.reshape(-1, res.shape[-1])
    # rate=0: the keep-mask operand is never read — alias x itself rather
    # than streaming a materialized all-ones buffer through VMEM
    out = _connective(
        x2, res2, x2, scale, bias, rate=0.0,
        block_s=block_s,
        interpret=_default_interpret(),
    )
    return out.reshape(*lead, x.shape[-1])


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128):
    return _flash(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=_default_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def tiled_gemm(x, w, *, block_m=256, block_n=256, block_k=512):
    return _gemm(
        x, w, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=_default_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("rate", "eps", "block_s"))
def fused_connective(x, res, keep_mask, scale, bias, *, rate=0.0, eps=1e-5, block_s=256):
    return _connective(
        x, res, keep_mask, scale, bias, rate=rate, eps=eps, block_s=block_s,
        interpret=_default_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("block_s", "block_w"))
def rglru_scan(a, b, h0, *, block_s=256, block_w=256):
    from repro.kernels.rglru_scan import rglru_scan_kernel

    return rglru_scan_kernel(
        a, b, h0, block_s=block_s, block_w=block_w,
        interpret=_default_interpret(),
    )
