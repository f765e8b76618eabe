"""Valid-length GEMM + ragged flash attention: pad-content invariance.

Hypothesis property tests: the pad-shedding kernels must be *exactly*
invariant to the contents of pad regions — randomized garbage in pad
rows/columns/heads cannot leak into valid outputs, which must stay allclose
to the ``kernels/ref.py`` oracles over the compacted (valid-only) operands.
That is the correctness contract that lets the executor skip masking
entirely on the pallas backend.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.execplan import SeqLayout  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import ragged_flash_attention  # noqa: E402
from repro.kernels.tiled_gemm import tiled_gemm_valid  # noqa: E402


@settings(max_examples=20, deadline=None)
@given(
    data=st.data(),
    m=st.integers(2, 6),
    n=st.integers(2, 6),
    k=st.integers(2, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_valid_gemm_invariant_to_pad_contents(data, m, n, k, seed):
    """Garbage in the pad regions of x and w changes nothing: valid output
    region == dense ref over zero-compacted operands, pad region == 0."""
    bm, bn, bk = 4, 4, 4
    m, n, k = m * bm, n * bn, k * bk
    vm = data.draw(st.integers(1, m), label="valid_m")
    vn = data.draw(st.integers(1, n), label="valid_n")
    vk = data.draw(st.integers(1, k), label="valid_k")
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(m, k)).astype(np.float32)
    w = rng.normal(size=(k, n)).astype(np.float32)
    # clean operands: zeros in every pad region (what zero-padded weights
    # and scattered activations hold in the real executor)
    xc = x.copy()
    xc[vm:] = 0
    xc[:, vk:] = 0
    wc = w.copy()
    wc[vk:] = 0
    wc[:, vn:] = 0
    expected = np.asarray(ref.tiled_gemm_ref(jnp.asarray(xc), jnp.asarray(wc)))
    # garbage operands: random junk in the same pad regions
    xg = x.copy()
    xg[vm:] = rng.normal(size=(m - vm, k)) * 100
    xg[:, vk:] = rng.normal(size=(m, k - vk)) * 100
    wg = w.copy()
    wg[vk:] = rng.normal(size=(k - vk, n)) * 100
    wg[:, vn:] = rng.normal(size=(k, n - vn)) * 100

    out = tiled_gemm_valid(
        jnp.asarray(xg), jnp.asarray(wg), valid_m=vm, valid_n=vn, valid_k=vk,
        block_m=bm, block_n=bn, block_k=bk, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-4)
    assert not np.any(np.asarray(out)[vm:])
    assert not np.any(np.asarray(out)[:, vn:])


@settings(max_examples=15, deadline=None)
@given(
    tiles=st.lists(st.integers(0, 6), min_size=2, max_size=4).filter(
        lambda t: max(t) > 0),
    h=st.integers(1, 4),
    vh=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_ragged_flash_invariant_to_pad_contents(tiles, h, vh, seed):
    """Garbage in pad rows (positions == -1) and pad head slots beyond
    valid_heads never reaches valid outputs; valid rows of valid heads
    match flash_attention_ref over the compacted sequence."""
    vh = min(vh, h)
    lay = SeqLayout(tuple(tiles))
    s, hd, b = lay.padded_len, 8, 2
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, h, s, hd)).astype(np.float32)
    k = rng.normal(size=(b, h, s, hd)).astype(np.float32)
    v = rng.normal(size=(b, h, s, hd)).astype(np.float32)
    pad = ~lay.valid
    qg, kg, vg = q.copy(), k.copy(), v.copy()
    for a in (qg, kg, vg):
        a[:, :, pad] = rng.normal(size=(b, h, int(pad.sum()), hd)) * 100
        a[:, vh:] = rng.normal(size=(b, h - vh, s, hd)) * 100

    out = ragged_flash_attention(
        jnp.asarray(qg), jnp.asarray(kg), jnp.asarray(vg),
        positions=lay.positions, valid_heads=vh, block_q=4, block_k=4,
        interpret=True,
    )
    out = np.asarray(out)
    assert not np.any(out[:, :, pad]), "pad rows must be exactly zero"
    assert not np.any(out[:, vh:]), "pad head slots must be exactly zero"
    if lay.seq:
        qc = jnp.asarray(q[:, :vh][:, :, lay.rows])
        kc = jnp.asarray(k[:, :vh][:, :, lay.rows])
        vc = jnp.asarray(v[:, :vh][:, :, lay.rows])
        expected = np.asarray(ref.flash_attention_ref(qc, kc, vc, causal=True))
        np.testing.assert_allclose(out[:, :vh][:, :, lay.rows], expected,
                                   atol=1e-5)
