"""The main-path Pallas kernels compile for a TPU v5e at the widths served.

The compile cases hand the kernels to the TPU compiler for a chip that is
described, not attached (``jax.experimental.topologies``): Mosaic refuses
blocks that break its (8, 128) tiling or store scalars to VMEM, which
interpret mode never sees.  The topology is described inside a
module-scoped fixture, so every test worker collects the same tests and
only the worker given this file loads the TPU library; where it cannot be
described the compile cases skip.  The persistent compilation cache is off
around these compiles (an entry compiled for a described chip cannot be
read back without one).

The interpret-mode cases pin the aligned block choice at extents the
blocks do not divide (gpt2-l's K=1280, qwen1.5-0.5b's 704 columns per chip
on four chips): the masked tail blocks leave the result bitwise equal to
the padded-XLA oracle (integer-valued operands make every summation order
exact).

The Galaxy executor's paged programs (decode, paged and chunked prefill)
are compiled the same way at gpt2-l widths: the compiled program must not
relayout a whole layer's K/V pool, which a pool stored in another layout
than its scatter and gather use costs every step.  So are the DeepSeek-V3
block's decode and paged prefill at moonlight-16b-a3b widths, against its
latent pool.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hmp
from repro.core.execplan import ExecPlan
from repro.kernels import ops
from repro.kernels.flash_attention import ragged_flash_attention
from repro.kernels.fused_connective import fused_connective
from repro.kernels.tiled_gemm import LANE, SUBLANE, aligned_block, tiled_gemm_valid
from repro.serving import galaxy


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


def _gemm_stack(x, wqkv, wo, w1, w2, heads, cols):
    """The four per-shard GEMMs of one Galaxy layer (pallas backend)."""
    hd = 64
    return (tiled_gemm_valid(x, wqkv, valid_m=x.shape[1], valid_n=heads * hd,
                             seg_n=wqkv.shape[1] // 3),
            tiled_gemm_valid(x, wo, valid_k=heads * hd),
            tiled_gemm_valid(x, w1, valid_n=cols),
            tiled_gemm_valid(x @ w1, w2, valid_k=cols))


def _qwen_chip_gemms(x, w1, w2, cols):
    # qwen1.5-0.5b's d_ff 2816 over four chips: 704 columns each
    return (tiled_gemm_valid(x, w1, valid_n=cols),
            tiled_gemm_valid(x @ w1, w2, valid_k=cols))


def _ragged(q, k, v, valid_heads):
    return ragged_flash_attention(q, k, v, positions=np.arange(q.shape[2]),
                                  valid_heads=valid_heads)


def _connective(x, res, scale, bias):
    return fused_connective(x, res, x, scale, bias)


# (case, fn, (shape, dtype) per argument); the kernels are called with
# interpret=False (the default), so they lower to Mosaic
CASES = {
    # gpt2-l on one chip: d_model 1280 (K=1280 leaves a masked 256-row
    # tail of the 512-deep K blocks), 20 heads x 64, d_ff 5120, 512 rows
    "gemm-gpt2l-prefill": (
        _gemm_stack,
        [((1, 512, 1280), jnp.float32), ((1280, 3840), jnp.float32),
         ((1280, 1280), jnp.float32), ((1280, 5120), jnp.float32),
         ((5120, 1280), jnp.float32), ((), jnp.int32), ((), jnp.int32)]),
    "gemm-qwen-704-per-chip": (
        _qwen_chip_gemms,
        [((1, 512, 1024), jnp.bfloat16), ((1024, 704), jnp.bfloat16),
         ((704, 1024), jnp.bfloat16), ((), jnp.int32)]),
    # decode: a batch of 8 slots is the M axis (8 rows, one sublane tile)
    "gemm-decode-batch8": (
        lambda x, w1, cols: tiled_gemm_valid(x, w1, valid_n=cols),
        [((1, 8, 1280), jnp.float32), ((1280, 5120), jnp.float32),
         ((), jnp.int32)]),
    "ragged-flash-1x20x1024x64": (
        _ragged,
        [((1, 20, 1024, 64), jnp.float32)] * 3 + [((), jnp.int32)]),
    "fused-connective-1024x1280": (
        _connective,
        [((1024, 1280), jnp.float32)] * 2 + [((1280,), jnp.float32)] * 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = CASES[case]
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("extent,preferred,align,block", [
    (1280, 512, LANE, 512),    # K: 2.5 blocks, the tail is masked
    (704, 128, LANE, 128),     # N: 5.5 blocks
    (8, 128, SUBLANE, 8),      # a decode batch spans its axis
    (1, 128, SUBLANE, 1),
    (200, 128, SUBLANE, 128),
    (300, 100, SUBLANE, 96),   # rounded down to the sublane unit
])
def test_aligned_block(extent, preferred, align, block):
    assert aligned_block(extent, preferred, align) == block


@pytest.mark.parametrize("m,k,n,valid_k,valid_n", [
    (64, 1280, 256, None, None),
    (64, 1280, 256, 1100, None),
    (64, 256, 704, None, 650),
    (8, 1280, 704, 1000, 600),
])
def test_valid_gemm_tail_blocks_bitwise(m, k, n, valid_k, valid_n):
    rng = np.random.default_rng(k + n)
    x = jnp.asarray(rng.integers(-3, 4, (1, m, k)), jnp.float32)
    w = jnp.asarray(rng.integers(-3, 4, (k, n)), jnp.float32)
    out = tiled_gemm_valid(x, w, valid_k=valid_k, valid_n=valid_n,
                           interpret=True)
    oracle = ops.gemm(x, w, backend="xla", valid_k=valid_k, valid_n=valid_n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


# --- the Galaxy paged pool keeps its layout through every paged program -------

# gpt2-l widths, depth cut to 2 layers: 8 slots x 8 pages of 128 positions
# (plus the null page), a 256-token prompt
_GPT2L = dict(num_heads=20, d_ff=5120, head_dim=64, d_model=1280)
_VOCAB, _LAYERS, _SLOTS, _ROW, _PAGE, _PROMPT = 50304, 2, 8, 8, 128, 256


def _paged_program(program, mesh, plan):
    """The executor's jitted paged program and its argument shapes."""
    def sharded(tree, spec_of):
        return jax.tree.map(lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree, spec_of)

    layer = jax.eval_shape(lambda: plan.pad_layer_params(hmp.init_layer_params(
        jax.random.PRNGKey(0), plan.d_model, plan.num_heads, plan.d_ff)))
    layer = sharded(layer, {k: hmp.layer_param_specs()[k] for k in layer})
    pool = jax.eval_shape(lambda: hmp.make_paged_kv_cache(
        1 + _SLOTS * _ROW, _PAGE, _LAYERS, mesh, plan))
    pool = sharded(pool, jax.tree.map(lambda _: hmp.PAGED_CACHE_SPEC, pool))
    rep = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=NamedSharding(mesh, P()))
    embed = jax.ShapeDtypeStruct((_VOCAB, plan.d_model), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    head = ([layer] * _LAYERS, embed)
    if program == "decode":
        fn = galaxy.paged_decode_program(mesh, plan)
        args = (*head, rep(_SLOTS, 1), pool, rep(_SLOTS, _ROW), rep(_SLOTS))
    elif program == "prefill_paged":
        fn = galaxy.paged_prefill_program(mesh, plan, True, _PROMPT)
        args = (*head, rep(1, _PROMPT), pool, rep(_ROW), rep())
    else:
        fn = galaxy.chunk_prefill_program(mesh, plan, True, _PROMPT)
        args = (*head, rep(1, _PROMPT), pool, rep(_ROW), rep(), rep())
    return fn, args, pool


@pytest.mark.parametrize("program", ["decode", "prefill_paged", "prefill_chunk"])
def test_galaxy_paged_program_keeps_the_pool_layout(one_chip, program):
    """Paged decode and paged (and chunked) prefill read and write the K/V
    pool in the layout it is stored in: no ``copy`` of a whole layer's pool
    in the compiled program, and every pool parameter leaves in the layout
    it came in (the scatter runs in place on the donated buffer)."""
    mesh = Mesh(np.array(list(one_chip.device_set)), ("model",))
    plan = ExecPlan.even(1, **_GPT2L)
    fn, args, pool = _paged_program(program, mesh, plan)
    compiled = fn.lower(*args).compile()

    shape = pool[0]["k"].shape
    pool_hlo = re.escape("f32[%s]" % ",".join(map(str, shape)))
    copies = re.findall(rf"= {pool_hlo}\{{[^}}]*\}} copy\(", compiled.as_text())
    assert not copies, f"{len(copies)} whole-pool copies in {program}"

    (ins, _), outs = compiled.input_formats, compiled.output_formats
    for layer_in, layer_out in zip(ins[3], outs[1]):
        for kv in ("k", "v"):
            assert layer_in[kv].layout == layer_out[kv].layout


# --- the DeepSeek-V3 block's paged programs ----------------------------------

# moonlight-16b-a3b widths, depth cut to the dense layer and one MoE layer
# holding 16 of 64 experts; the cell's 16 slots x 32 pages of 256 positions
# (plus the null page); a 512-token prompt
_MLA_SLOTS, _MLA_ROW, _MLA_PAGE, _MLA_PROMPT = 16, 32, 256, 512


def _mla_program(program, mesh):
    import dataclasses

    from repro.configs import get_config
    from repro.core import mla_moe

    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), num_layers=2,
                              experts_held=16, vocab_size=40960)
    layers, vocab = jax.eval_shape(
        lambda: mla_moe.init_params(cfg, jax.random.PRNGKey(0)))
    put = lambda a, spec: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec))
    layers = [jax.tree.map(put, l, mla_moe.layer_specs(l)) for l in layers]
    vocab = jax.tree.map(lambda a: put(a, P()), vocab)
    lanes = mla_moe.pool_lanes(layers[0])
    pool = [{"c": jax.ShapeDtypeStruct(
        (1 + _MLA_SLOTS * _MLA_ROW, _MLA_PAGE, lanes), jnp.float32,
        sharding=NamedSharding(mesh, P()))} for _ in layers]
    rep = lambda *shape: put(jax.ShapeDtypeStruct(shape, jnp.int32), P())
    load = rep(1, cfg.experts_held)
    if program == "decode":
        fn = galaxy.mla_decode_program(mesh)
        args = (layers, vocab, rep(_MLA_SLOTS, 1), pool,
                rep(_MLA_SLOTS, _MLA_ROW), rep(_MLA_SLOTS), load)
    else:
        fn = galaxy.mla_prefill_program(mesh, _MLA_PROMPT)
        args = (layers, vocab, rep(1, _MLA_PROMPT), pool, rep(_MLA_ROW),
                rep(), load)
    return fn, args, pool


@pytest.mark.parametrize("program", ["decode", "prefill_paged"])
def test_mla_paged_program_compiles_and_keeps_the_pool_layout(one_chip,
                                                              program):
    """The MLA/MoE block's decode and paged prefill compile for a v5e at the
    model's widths, run the held experts as grouped GEMMs (a ragged dot),
    and write the latent pool in place: no ``copy`` of a layer's pool, and
    every pool parameter leaves in the layout it came in."""
    mesh = Mesh(np.array(list(one_chip.device_set)), ("model",))
    fn, args, pool = _mla_program(program, mesh)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "ragged" in text
    pool_hlo = re.escape("f32[%s]" % ",".join(map(str, pool[0]["c"].shape)))
    copies = re.findall(rf"= {pool_hlo}\{{[^}}]*\}} copy\(", text)
    assert not copies, f"{len(copies)} whole-pool copies in {program}"
    (ins, _), outs = compiled.input_formats, compiled.output_formats
    for layer_in, layer_out in zip(ins[3], outs[1]):
        assert layer_in["c"].layout == layer_out["c"].layout
