"""The DeepSeek-V3 block (latent attention + dropless mixture of experts) on
the Galaxy serving path, at a tiny size on the CPU, against the plain
reference (``models/deepseek_v3.py``).

Tolerances are float32's: the program and the reference differ only in
summation order (the executor runs its matmuls at JAX's default precision,
which on the CPU is float32), so logits agree to about 1e-6 of a row's
spread; the bound 1e-4 leaves a hundred times that, and a wrong key
position, a skipped norm or a lost expert moves logits by a whole spread.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_mla_moe
from repro.core import mla_moe
from repro.core.execplan import ExecPlan
from repro.launch.mesh import make_mesh_compat
from repro.models import deepseek_v3 as ref
from repro.serving import (GalaxyHMPExecutor, GalaxyMLAExecutor, Request,
                           ServingEngine)

TOL = 1e-4  # of the reference row's standard deviation


@pytest.fixture(scope="module")
def cfg():
    return reduced_mla_moe(get_config("moonlight-16b-a3b"))


@pytest.fixture(scope="module")
def weights(cfg):
    return jax.jit(lambda k: mla_moe.init_params(cfg, k))(
        jax.random.PRNGKey(3))


_forward = jax.jit(ref.forward, static_argnums=0)


def _executor(cfg, weights):
    layers, vocab = weights
    plan = ExecPlan.even(1, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                         head_dim=cfg.v_head_dim, d_model=cfg.d_model)
    return GalaxyMLAExecutor(layers, vocab, plan,
                             make_mesh_compat((1,), ("model",)))


def _reference(cfg, weights, seq):
    layers, vocab = weights
    return np.asarray(_forward(
        cfg, [l.p for l in layers],
        {"table": vocab.table, "norm": vocab.norm, "head": vocab.head},
        np.asarray(seq)))


class Rows:
    """Executor wrapper keeping each call's logits with what places them:
    a prefill's prompt, a decode step's first page and position per row."""

    def __init__(self, inner):
        self.inner = inner
        self.prefills, self.decodes = [], []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def prefill_paged(self, tokens, pool, block_row, length):
        logits, pool = self.inner.prefill_paged(tokens, pool, block_row,
                                                length)
        self.prefills.append((np.asarray(tokens)[0, :length],
                              int(block_row[0]), np.asarray(logits)[0]))
        return logits, pool

    def decode_paged(self, tokens, pool, block_table, positions):
        logits, pool = self.inner.decode_paged(tokens, pool, block_table,
                                               positions)
        self.decodes.append((np.asarray(block_table)[:, 0],
                             np.asarray(positions), np.asarray(logits)))
        return logits, pool

    def served(self, r):
        """The logits rows behind request ``r``'s tokens, in order."""
        prompt = np.asarray(r.prompt)
        (page, row), = [(p, lg) for t, p, lg in self.prefills
                        if np.array_equal(t, prompt)]
        out, at = [row], len(r.prompt)
        for pages, pos, logits in self.decodes:
            i = np.flatnonzero((pages == page) & (pos == at))
            if i.size and len(out) < len(r.output):
                out.append(logits[i[0]])
                at += 1
        return np.stack(out)


def test_engine_prefill_then_decode_matches_reference(cfg, weights):
    """Paged prefill, then decode through ``ServingEngine`` on
    ``GalaxyMLAExecutor`` with continuous batching over the latent pool:
    every served token's logits against the reference's full forward."""
    ex = Rows(_executor(cfg, weights))
    eng = ServingEngine(executor=ex, max_batch=3, max_len=48, page_size=8)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(5, 9), (13, 4), (21, 12), (8, 6)])]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        want = _reference(cfg, weights, r.prompt + r.output[:-1])[
            len(r.prompt) - 1:]
        got = ex.served(r)
        sd = want.std(axis=1, keepdims=True)
        assert np.abs(got - want).max(axis=1).max() <= TOL * sd.min(), r.uid
        assert r.output == want.argmax(-1).tolist()
    load = ex.read_moe_load()
    counters = ex.metrics.snapshot()["counters"]
    assert load.shape == (cfg.num_layers - cfg.first_dense, cfg.experts_held)
    assert counters["moe.assignments_here"] == load.sum() > 0
    assert counters["moe.tokens_routed"] > 0
    assert counters["mla.latent_positions_read"] > 0


def test_absorbed_decode_matches_expanded_attention(cfg, weights):
    """Decode's attention in the latent space (``wk_b`` absorbed into the
    query, ``wv_b`` into the output) equals the expanded per-head keys and
    values over the same pool rows."""
    p, b = weights[0][1].p, weights[0][1].block
    s = 11
    rng = np.random.default_rng(1)
    xn = jnp.asarray(rng.standard_normal((s, cfg.d_model)), jnp.float32)
    pos = jnp.arange(s)
    lanes = mla_moe.pool_lanes(weights[0][1])
    rows = mla_moe.latent(p, xn, pos, b, lanes)
    q_nope, q_pe = mla_moe.query(p, xn, pos, b)
    k, v = mla_moe.expand(p, rows)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    expanded = mla_moe.expanded_attention(
        jnp.concatenate([q_nope, q_pe], -1), k, v, pos, pos,
        jnp.ones(s, bool), scale)
    valid = pos[None, :] <= pos[:, None]
    absorbed = mla_moe.absorbed_attention(
        p, q_nope, q_pe, jnp.broadcast_to(rows, (s, s, lanes)), valid)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=0, atol=1e-5)


def test_dropless_a_token_alone_equals_in_a_batch_on_the_same_experts(
        cfg, weights):
    """No capacity: a batch whose every token routes to the same experts
    (one prompt in every slot) gives each row the logits of that token
    served alone, with the other slots idle."""
    ex = _executor(cfg, weights)
    slots, width, page = 6, 4, 8
    pool = ex.make_pool(1 + slots * width, page)
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 10)
    table = np.arange(1, 1 + slots * width, dtype=np.int32).reshape(
        slots, width)
    toks = jnp.asarray(np.pad(prompt, (0, 6))[None], jnp.int32)
    for s in range(slots):
        _, pool = ex.prefill_paged(toks, pool, jnp.asarray(table[s]), 10)
    step = (jnp.full((slots, 1), 7, jnp.int32), jnp.full(slots, 10,
                                                          jnp.int32))
    full, pool = ex.decode_paged(step[0], pool, jnp.asarray(table), step[1])
    ids = np.asarray(ex.routing)
    assert (ids == ids[:, :1]).all()  # every row chose the same experts
    alone_table = np.where(np.arange(slots)[:, None] == 0, table, 0)
    alone, _ = ex.decode_paged(step[0], pool, jnp.asarray(alone_table),
                               jnp.where(jnp.arange(slots) == 0, 10, 0))
    np.testing.assert_allclose(np.asarray(full),
                               np.broadcast_to(np.asarray(alone)[:1],
                                               full.shape),
                               rtol=0, atol=1e-5)


def test_expert_shares_add_up_to_the_uncut_layer(cfg):
    """Expert parallelism over 4 shares: each share's routed part (the
    program's grouped GEMMs over the experts it holds), plus the shared
    experts counted once, gives the reference layer that holds every
    expert; the reference's own share holds the same."""
    full = dataclasses.replace(cfg, experts_held=cfg.num_experts)
    layers, _ = jax.jit(lambda k: mla_moe.init_params(full, k))(
        jax.random.PRNGKey(4))
    layer = layers[-1]
    p = layer.p
    xn = jnp.asarray(np.random.default_rng(3).standard_normal(
        (17, cfg.d_model)), jnp.float32)
    moe = jax.jit(ref.moe, static_argnums=0)
    routed_experts = jax.jit(mla_moe.routed_experts)
    want = np.asarray(moe(full, p, xn))
    ids, w = mla_moe.route(p, xn, layer.block)
    shared = np.asarray(mla_moe.swiglu(xn, p["s_gate"], p["s_up"],
                                       p["s_down"]))
    per = cfg.num_experts // 4
    routed, ref_routed = 0.0, 0.0
    for d in range(4):
        cut = slice(d * per, (d + 1) * per)
        share = {**p, "held": p["held"][cut], "e_gate": p["e_gate"][cut],
                 "e_up": p["e_up"][cut], "e_down": p["e_down"][cut]}
        part, sizes = routed_experts(share, xn, ids, w)
        assert int(sizes.sum()) == int(np.isin(np.asarray(ids),
                                               np.asarray(share["held"])).sum())
        routed = routed + np.asarray(part)
        ref_routed = ref_routed + np.asarray(moe(full, share, xn)) - shared
    np.testing.assert_allclose(routed + shared, want, rtol=0, atol=1e-5)
    np.testing.assert_allclose(ref_routed + shared, want, rtol=0, atol=1e-5)


def test_chunked_prefill_matches_the_one_shot_prefill(cfg, weights):
    """``prefill_chunk`` at an offset attends back to the latent rows the
    earlier chunk wrote: its last row's logits equal the one-shot prefill's
    and the reference's."""
    ex = _executor(cfg, weights)
    pool = ex.make_pool(9, 8)
    row = jnp.arange(1, 5, dtype=jnp.int32)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 16)
    one, _ = ex.prefill_paged(jnp.asarray(prompt[None]), ex.make_pool(9, 8),
                              row, 16)
    _, pool = ex.prefill_chunk(jnp.asarray(prompt[None, :8]), pool, row,
                               offset=0, length=16)
    last, _ = ex.prefill_chunk(jnp.asarray(prompt[None, 8:]), pool, row,
                               offset=8, length=16)
    want = _reference(cfg, weights, prompt)[-1]
    np.testing.assert_allclose(np.asarray(last)[0, -1], np.asarray(one)[0],
                               rtol=0, atol=TOL * want.std())
    np.testing.assert_allclose(np.asarray(one)[0], want, rtol=0,
                               atol=TOL * want.std())


def test_galaxy_serve_builds_the_real_block_and_refuses_others(
        cfg, monkeypatch):
    """``launch/serve.py --executor galaxy`` builds the DeepSeek-V3 block for
    an MLA/MoE arch and the paper layer for the paper's models, and refuses
    an arch whose block the Galaxy path does not implement."""
    from repro.configs import get_config, reduced
    from repro.launch import serve

    # the paper layer's plan spans every local device: one here, whatever
    # host devices an earlier import in this process forced
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    ex = serve._galaxy_executor(cfg, "xla")
    assert isinstance(ex, GalaxyMLAExecutor)
    assert isinstance(ex.layers[0], mla_moe.MLALayer)
    paper = serve._galaxy_executor(reduced(get_config("gpt2-l")), "xla")
    assert type(paper) is GalaxyHMPExecutor and "wq" in paper.layers[0]
    with pytest.raises(SystemExit, match="not this arch's block"):
        serve._galaxy_executor(reduced(get_config("qwen1.5-0.5b")), "xla")
    plan = ExecPlan.even(2, num_heads=cfg.num_heads, d_ff=cfg.d_ff,
                         head_dim=cfg.v_head_dim, d_model=cfg.d_model)
    with pytest.raises(NotImplementedError, match="one-device plan"):
        GalaxyMLAExecutor(ex.layers, ex.embed, plan,
                          make_mesh_compat((1,), ("model",)))
