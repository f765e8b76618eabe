"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in kernels/ref.py (interpret mode executes the kernel body)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_connective import fused_connective
from repro.kernels.tiled_gemm import tiled_gemm

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize(
    "b,h,hkv,sq,sk,hd,causal,window",
    [
        (1, 4, 4, 128, 128, 64, True, 0),
        (2, 8, 2, 128, 128, 64, True, 0),       # GQA 4:1
        (1, 4, 1, 128, 256, 32, True, 0),       # MQA, right-aligned decode-ish
        (1, 4, 4, 128, 128, 64, True, 32),      # sliding window
        (1, 2, 2, 64, 128, 128, False, 0),      # cross-attn (no mask)
        (1, 16, 2, 256, 256, 64, True, 64),
    ],
)
def test_flash_attention_sweep(b, h, hkv, sq, sk, hd, causal, window):
    q = jax.random.normal(KEY, (b, h, sq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, sk, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, sk, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-6)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 5e-4), (jnp.bfloat16, 0.25)])
@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (256, 512, 384), (512, 128, 256)])
def test_tiled_gemm_sweep(m, k, n, dtype, atol):
    x = jax.random.normal(KEY, (m, k)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(dtype)
    out = tiled_gemm(x, w, block_m=128, block_n=128, block_k=128, interpret=True)
    expected = ref.tiled_gemm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32), atol=atol
    )


@pytest.mark.parametrize("s,d", [(256, 128), (512, 256), (128, 512)])
@pytest.mark.parametrize("rate", [0.0, 0.1])
def test_fused_connective_sweep(s, d, rate):
    x = jax.random.normal(KEY, (s, d), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(1), (s, d), jnp.float32)
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (s, d)) > rate).astype(jnp.float32)
    scale = jnp.ones((d,)) * 1.3
    bias = jnp.zeros((d,)) + 0.05
    out = fused_connective(x, res, mask, scale, bias, rate=rate, block_s=128,
                           interpret=True)
    expected = ref.fused_connective_ref(x, res, mask, scale, bias, rate=rate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_kernel_shape_errors_are_valueerrors():
    """Bad tilings raise ValueError naming shapes/blocks (not a bare assert
    that vanishes under ``python -O``)."""
    x = jnp.zeros((100, 64))
    w = jnp.zeros((64, 96))
    with pytest.raises(ValueError, match="block_m=48"):
        tiled_gemm(x, w, block_m=48, block_n=32, block_k=32, interpret=True)
    with pytest.raises(ValueError, match="contraction mismatch"):
        tiled_gemm(jnp.zeros((64, 32)), jnp.zeros((48, 96)), interpret=True)
    q = jnp.zeros((1, 2, 100, 64))
    with pytest.raises(ValueError, match="block_q=32"):
        flash_attention(q, q, q, block_q=32, block_k=50, interpret=True)
    with pytest.raises(ValueError, match="block_s"):
        fused_connective(jnp.zeros((100, 8)), jnp.zeros((100, 8)),
                         jnp.zeros((100, 8)), jnp.ones(8), jnp.zeros(8),
                         block_s=12, interpret=True)
    from repro.kernels.tiled_gemm import tiled_gemm_valid

    with pytest.raises(ValueError, match="seg_n"):
        tiled_gemm_valid(x, w, seg_n=40, interpret=True)


def test_valid_gemm_matches_dense_when_fully_valid():
    """With no valid counts the valid-length kernel is the dense GEMM."""
    x = jax.random.normal(KEY, (64, 96), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 128), jnp.float32)
    from repro.kernels.tiled_gemm import tiled_gemm_valid

    out = tiled_gemm_valid(x, w, block_m=32, block_n=32, block_k=32,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.tiled_gemm_ref(x, w)),
                               atol=5e-4)


@pytest.mark.parametrize("m,n,k,kw,blocks", [
    # fused QKV: three 512-column segments, 320 valid each -> 3 of 4 N
    # blocks per segment; K=1280 takes 3 blocks of 512 (masked tail)
    (64, 1536, 1280, dict(valid_n=320, seg_n=512), 1 * 9 * 3),
    # 192-column segments: block 1 straddles into segment 1's valid
    # prefix, block 3 starts segment 2 -> blocks 0, 1, 3 live
    (8, 576, 256, dict(valid_n=32, seg_n=192), 1 * 3 * 1),
    (200, 256, 1280, dict(valid_m=5, valid_k=100), 1 * 2 * 1),
])
def test_dense_block_count(m, n, k, kw, blocks):
    """The analytic live-block count under the kernel's aligned blocks."""
    from repro.kernels.tiled_gemm import dense_block_count

    assert dense_block_count(m, n, k, **kw) == blocks


def test_ops_gemm_backend_dispatch():
    """ops.gemm: xla == pallas on clean (zero-padded) operands; batched
    inputs are M segments with their own valid rows, or fold into one M
    axis when every row is valid; unknown backends are rejected."""
    x = jax.random.normal(KEY, (2, 8, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 12), jnp.float32)
    w = w.at[:, 9:].set(0)  # pad columns zero, as ExecPlan materializes
    dense = ops.gemm(x, w, backend="xla")
    shed = ops.gemm(x, w, backend="pallas", valid_n=9, block_n=3)
    np.testing.assert_allclose(np.asarray(shed), np.asarray(dense), atol=1e-5)
    xr = x.at[:, 5:].set(0)  # each batch row's segment holds 5 real rows
    shed_m = ops.gemm(xr, w, backend="pallas", valid_m=5, valid_n=9)
    np.testing.assert_allclose(np.asarray(shed_m),
                               np.asarray(ops.gemm(xr, w, backend="xla")),
                               atol=1e-5)
    with pytest.raises(ValueError, match="backend"):
        ops.gemm(x, w, backend="cuda")


def test_ops_wrappers_jit():
    """The public ops wrappers are jit-compatible on this backend."""
    q = jax.random.normal(KEY, (1, 2, 128, 64))
    out = ops.flash_attention(q, q, q)
    assert out.shape == q.shape
    x = jax.random.normal(KEY, (256, 256))
    assert ops.tiled_gemm(x, x).shape == (256, 256)


@pytest.mark.parametrize(
    "b,s,w,bs,bw",
    [(2, 128, 64, 32, 32), (1, 256, 128, 64, 128), (3, 64, 96, 64, 32)],
)
def test_rglru_scan_kernel_sweep(b, s, w, bs, bw):
    from repro.kernels.rglru_scan import rglru_scan_kernel

    a = jax.random.uniform(KEY, (b, s, w), minval=0.5, maxval=0.99)
    bb = jax.random.normal(jax.random.PRNGKey(1), (b, s, w))
    h0 = jax.random.normal(jax.random.PRNGKey(2), (b, w))
    hs, hl = rglru_scan_kernel(a, bb, h0, block_s=bs, block_w=bw, interpret=True)
    rs, rl = ref.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(rs), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(rl), atol=1e-5)


def test_rglru_scan_kernel_matches_model_scan():
    """The Pallas kernel agrees with the model's associative_scan path."""
    from repro.kernels.rglru_scan import rglru_scan_kernel
    from repro.models.rglru import rglru_scan as assoc_scan

    b, s, w = 2, 64, 32
    a = jax.random.uniform(KEY, (b, s, w), minval=0.3, maxval=0.999)
    bb = jax.random.normal(jax.random.PRNGKey(1), (b, s, w))
    h0 = jax.random.normal(jax.random.PRNGKey(2), (b, w))
    hs_k, hl_k = rglru_scan_kernel(a, bb, h0, block_s=32, block_w=32, interpret=True)
    hs_a, hl_a = assoc_scan(a.astype(jnp.float32), bb.astype(jnp.float32), h0)
    np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_a), atol=1e-4)
    np.testing.assert_allclose(np.asarray(hl_k), np.asarray(hl_a), atol=1e-4)
