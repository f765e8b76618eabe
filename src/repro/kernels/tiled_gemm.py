"""MXU-aligned tiled GEMM Pallas kernels.

This is the compute primitive of the paper's tile-based overlap (§III-D):
each ring step's per-tile GEMM is exactly one of these calls on a sequence
tile.  BlockSpecs stage (block_m x block_k) / (block_k x block_n) operand
tiles into VMEM with a fp32 VMEM accumulator; the k grid axis is innermost
so the accumulator lives across the contraction.  128-multiples align the
MXU's 128x128 systolic array.

Two entry points:

* :func:`tiled_gemm` — the dense kernel (all blocks computed).
* :func:`tiled_gemm_valid` — the *valid-length* kernel behind the
  ``compute_backend="pallas"`` ExecPlan path: per-device valid row/column/
  contraction counts enter as scalar-prefetch operands, the grid skips
  blocks that lie entirely in the pad region (no MXU issue), and the
  straddling block is masked in the epilogue.  A device holding 2 of
  max=4 padded head slots therefore runs ~half the MXU work of the pad-and-mask SPMD oracle instead of a
  mask-multiply over the full padded shard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Mosaic tiles the last two dimensions of every VMEM block in (8, 128)
#: (sublane, lane) units: a block dimension must be a multiple of its unit
#: or span the whole array axis
SUBLANE, LANE = 8, 128


def aligned_block(extent: int, preferred: int, align: int) -> int:
    """Block size along one kernel axis that Mosaic accepts.

    The whole axis when it fits in ``preferred`` (a full-extent block is
    always legal), else ``preferred`` rounded down to a multiple of
    ``align`` (at least one ``align``).  Grids then take
    ``cdiv(extent, block)`` steps: the last block may run past the array,
    and the kernels mask that tail instead of shrinking the block to an
    arbitrary divisor of the extent.
    """
    if extent <= 0:
        raise ValueError(f"cannot pick a block for extent {extent}")
    if extent <= preferred:
        return extent
    return min(extent, max(align, preferred - preferred % align))


def _validate_tiling(m: int, n: int, k: int, block_m: int, block_n: int,
                     block_k: int) -> None:
    # a bare assert would vanish under ``python -O`` and resurface as an
    # opaque XLA shape error; name the offending shapes/blocks instead
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"GEMM ({m}x{k}) @ ({k}x{n}) does not tile into blocks "
            f"(block_m={block_m}, block_n={block_n}, block_k={block_k}): "
            "every block size must divide its axis"
        )


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tiled_gemm(
    x, w, *, block_m: int = 256, block_n: int = 256, block_k: int = 512,
    interpret: bool = False,
):
    """x: (M, K) @ w: (K, N) -> (M, N), fp32 accumulation in VMEM."""
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(
            f"GEMM contraction mismatch: x is ({m}x{k}) but w is ({k2}x{n})"
        )
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    _validate_tiling(m, n, k, block_m, block_n, block_k)

    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, w)


# --- valid-length GEMM (the ExecPlan pad-shedding backend) --------------------

def _valid_kernel(v_ref, x_ref, w_ref, o_ref, acc_ref, *,
                  block_m: int, block_n: int, block_k: int,
                  n: int, k: int, seg_n: int):
    """Grid cell (g, mi, ni, ki); ``v_ref`` prefetches (valid_m, valid_n,
    valid_k).  ``g`` indexes the M segments (e.g. the batch rows of a
    sequence tile), each with a valid row *prefix*; the N axis is cut into
    ``seg_n``-column segments (e.g. the q/k/v column groups of a fused QKV
    weight), each with a valid column prefix.  A block that holds no valid
    row, column or contraction entry is pure padding and its dot is
    skipped."""
    mi = pl.program_id(1)
    ni = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    vm, vn, vk = v_ref[0], v_ref[1], v_ref[2]
    # an N block holds a valid column if it starts inside its segment's
    # valid prefix, or runs into the next segment (whose prefix starts at
    # its first column); blocks may straddle segments since lane-aligned
    # blocks need not divide seg_n
    col0 = ni * block_n
    col1 = jnp.minimum(col0 + block_n, n) - 1
    live_n = (col0 % seg_n < vn) | ((col0 // seg_n != col1 // seg_n) & (vn > 0))
    live = (mi * block_m < vm) & live_n & (ki * block_k < vk)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        xb = x_ref[...]
        # zero the contraction tail of the straddling K block so garbage in
        # pad columns of x cannot contribute
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, xb.shape, 1)
        xb = jnp.where(kpos < vk, xb, 0)
        wb = w_ref[...]
        if k % block_k:
            # the last K block reads past the end of w, where the buffer
            # may hold non-finite values that 0 * x would not cancel
            wpos = (ki * block_k
                    + jax.lax.broadcasted_iota(jnp.int32, wb.shape, 0))
            wb = jnp.where(wpos < k, wb, 0)
        acc_ref[...] += jnp.dot(xb, wb, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _epilogue():
        # mask the straddling M/N blocks: pad rows/columns come out exactly
        # zero no matter what the pad regions of x and w held (rows and
        # columns past the array end are dropped by the output store)
        rows = mi * block_m + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        cols = (ni * block_n + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 1)) % seg_n
        keep = (rows < vm) & (cols < vn)
        o_ref[...] = jnp.where(keep, acc_ref[...], 0).astype(o_ref.dtype)


def _valid_blocks(m: int, n: int, k: int, block_m: int, block_n: int,
                  block_k: int):
    return (aligned_block(m, block_m, SUBLANE), aligned_block(n, block_n, LANE),
            aligned_block(k, block_k, LANE))


def tiled_gemm_valid(
    x, w, *, valid_m=None, valid_n=None, valid_k=None,
    seg_n: int | None = None,
    block_m: int = 128, block_n: int = 128, block_k: int = 512,
    interpret: bool = False,
):
    """Valid-length (G, M, K) @ (K, N) -> (G, M, N) that sheds pad blocks.

    x may also be 2-D (M, K), one segment.  The leading axis holds G
    independent M segments (e.g. the batch rows of a sequence tile).

    valid_m: real leading rows of each M segment (traced scalar ok — it is
             a per-device quantity inside shard_map); pad rows of the
             output are exactly zero.
    valid_n: real leading columns of each ``seg_n``-column N segment; pad
             columns of the output are exactly zero.
    valid_k: real leading entries of the contraction axis; the pad tail
             contributes exactly zero regardless of operand contents.
    seg_n:   N segment extent (default: one segment spanning the axis).

    ``None`` valid counts mean fully dense on that axis.  Blocks are the
    preferred sizes, (8, 128)-aligned or spanning their axis
    (:func:`aligned_block`); tails past the array end are masked.
    """
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
    g, m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(
            f"GEMM contraction mismatch: x is ({m}x{k}) but w is ({k2}x{n})"
        )
    seg_n = n if seg_n is None else seg_n
    if n % seg_n:
        raise ValueError(
            f"N segments (seg_n={seg_n}) must divide the GEMM's {n} columns"
        )
    block_m, block_n, block_k = _valid_blocks(m, n, k, block_m, block_n,
                                              block_k)

    valid = jnp.stack([
        jnp.asarray(m if valid_m is None else valid_m, jnp.int32),
        jnp.asarray(seg_n if valid_n is None else valid_n, jnp.int32),
        jnp.asarray(k if valid_k is None else valid_k, jnp.int32),
    ])
    kernel = functools.partial(
        _valid_kernel, block_m=block_m, block_n=block_n, block_k=block_k,
        n=n, k=k, seg_n=seg_n,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, pl.cdiv(m, block_m), pl.cdiv(n, block_n), pl.cdiv(k, block_k)),
        in_specs=[
            pl.BlockSpec((None, block_m, block_k),
                         lambda gi, mi, ni, ki, v: (gi, mi, ki)),
            pl.BlockSpec((block_k, block_n),
                         lambda gi, mi, ni, ki, v: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((None, block_m, block_n),
                               lambda gi, mi, ni, ki, v: (gi, mi, ni)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, m, n), x.dtype),
        interpret=interpret,
    )(valid, x, w)
    return out[0] if squeeze else out


def dense_block_count(
    m: int, n: int, k: int, *, valid_m=None, valid_n=None, valid_k=None,
    seg_n: int | None = None,
    block_m: int = 128, block_n: int = 128, block_k: int = 512,
) -> int:
    """Analytic count of the (m, n, k) blocks :func:`tiled_gemm_valid`
    issues a dot for on one M segment of ``m`` rows — the blocks holding at
    least one valid row, column and contraction entry under the kernel's
    own block choice."""
    seg_n = n if seg_n is None else seg_n
    block_m, block_n, block_k = _valid_blocks(m, n, k, block_m, block_n,
                                              block_k)
    vm = m if valid_m is None else int(valid_m)
    vn = seg_n if valid_n is None else int(valid_n)
    vk = k if valid_k is None else int(valid_k)
    cols = np.arange(n) % seg_n < vn
    live_n = sum(bool(cols[c:c + block_n].any()) for c in range(0, n, block_n))
    return -(-vm // block_m) * live_n * -(-vk // block_k)
