"""PagedKVPool: allocation invariants (incl. hypothesis property test),
paged-decode == dense-decode token equality on the default executor, and
the Galaxy executor's pool storage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hmp
from repro.core.execplan import ExecPlan
from repro.launch.mesh import make_mesh_compat
from repro.models import init_params
from repro.serving import (GalaxyHMPExecutor, PagedKVPool, PoolExhausted,
                           Request, ServingEngine)
from repro.serving.kvpool import NULL_PAGE

from helpers import smoke_cfg


# --- deterministic bookkeeping ------------------------------------------------

def test_admit_ensure_retire_roundtrip():
    pool = PagedKVPool(num_pages=9, page_size=4, num_slots=2, pages_per_slot=4)
    assert pool.free_pages == 8  # page 0 reserved as the null page
    pool.admit(0, initial_positions=5, max_positions=13)  # 2 pages now, 4 max
    pool.check()
    assert pool.free_pages == 6 and pool.available == 4
    assert np.all(pool.block_table[0, :2] != NULL_PAGE)
    pool.ensure(0, 7)  # still within page 2
    assert pool.free_pages == 6
    pool.ensure(0, 8)  # crosses into page 3
    pool.check()
    assert pool.free_pages == 5
    pages = pool.retire(0)
    pool.check()
    assert len(pages) == 3 and pool.free_pages == 8 and pool.available == 8
    assert np.all(pool.block_table[0] == NULL_PAGE)


def test_reservation_blocks_oversubscription():
    pool = PagedKVPool(num_pages=5, page_size=4, num_slots=2, pages_per_slot=4)
    pool.admit(0, initial_positions=4, max_positions=12)  # 1 allocated, 3 reserved
    assert pool.available == 1
    assert not pool.can_admit(8)  # needs 2, only 1 admissible
    with pytest.raises(PoolExhausted):
        pool.admit(1, initial_positions=8, max_positions=8)
    pool.admit(1, initial_positions=4, max_positions=4)
    pool.check()
    # slot 0 can always grow into its reservation
    pool.ensure(0, 11)
    pool.check()
    with pytest.raises(PoolExhausted):
        pool.ensure(0, 12)  # beyond its own reservation


def test_retired_pages_are_reused():
    pool = PagedKVPool(num_pages=4, page_size=2, num_slots=1, pages_per_slot=3)
    pool.admit(0, 6, 6)
    first = pool.retire(0)
    pool.admit(0, 6, 6)
    second = pool.retire(0)
    assert sorted(first) == sorted(second)  # same physical pages recycled
    pool.check()


def test_request_larger_than_block_table_rejected():
    pool = PagedKVPool(num_pages=16, page_size=2, num_slots=1, pages_per_slot=2)
    assert not pool.can_admit(5)
    with pytest.raises(ValueError):
        pool.admit(0, 2, 5)


# --- hypothesis: random admit/retire sequences never leak -------------------

def test_random_lifecycle_never_leaks_or_double_allocates():
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.settings(max_examples=60, deadline=None)
    @hypothesis.given(
        ops=st.lists(
            st.tuples(st.sampled_from(["admit", "ensure", "retire"]),
                      st.integers(0, 3), st.integers(0, 40)),
            max_size=60,
        ),
        page_size=st.integers(1, 8),
        num_pages=st.integers(2, 24),
    )
    def run(ops, page_size, num_pages):
        pool = PagedKVPool(num_pages, page_size, num_slots=4, pages_per_slot=6)
        live = {}
        for op, slot, arg in ops:
            if op == "admit" and not pool.active[slot]:
                need = arg + 1
                if pool.can_admit(need):
                    pool.admit(slot, initial_positions=min(need, arg or 1),
                               max_positions=need)
                    live[slot] = need
            elif op == "ensure" and pool.active[slot]:
                pos = min(arg, live[slot] - 1)
                pool.ensure(slot, pos)
            elif op == "retire" and pool.active[slot]:
                pool.retire(slot)
                live.pop(slot)
            pool.check()
        for slot in list(live):
            pool.retire(slot)
        pool.check()
        assert pool.free_pages == num_pages - 1

    run()


# --- paged decode == dense decode, token for token ---------------------------

def _mixed_requests():
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(9):
        n = int(rng.integers(3, 14))
        reqs.append(Request(
            uid=i, prompt=[int(t) for t in rng.integers(1, 400, n)],
            max_new_tokens=int(rng.integers(1, 12)),
        ))
    return reqs


@pytest.mark.parametrize("page_size,num_pages", [(4, None), (8, 9)])
def test_paged_decode_matches_dense_decode(page_size, num_pages):
    """Continuous batching over the paged pool produces greedy tokens
    identical to the dense-cache wave path — including with a deliberately
    tight pool (num_pages=9) that forces admission to wait on capacity."""
    cfg = smoke_cfg("qwen1.5-0.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def run(scheduler):
        eng = ServingEngine(params, cfg, max_batch=3, max_len=32,
                            scheduler=scheduler, page_size=page_size,
                            num_pages=num_pages)
        for r in _mixed_requests():
            eng.submit(r)
        done = eng.run()
        assert all(r.done for r in done) and len(done) == 9
        return {r.uid: r.output for r in done}, eng.stats

    dense, _ = run("wave")
    paged, stats = run("continuous")
    assert paged == dense
    # every token beyond each request's first (sampled off prefill logits)
    # came from a continuous decode step
    assert stats["decode_steps"] > 0
    assert stats["decode_tokens"] == sum(len(v) for v in paged.values()) - 9


def test_pool_too_small_for_one_request_raises():
    cfg = smoke_cfg("qwen1.5-0.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2, max_len=32,
                        scheduler="continuous", page_size=4, num_pages=3)
    eng.submit(Request(uid=0, prompt=list(range(1, 17)), max_new_tokens=8))
    with pytest.raises(RuntimeError, match="cannot fit"):
        eng.run()


def test_galaxy_pool_rows_hold_each_position_kv_flattened():
    """The Galaxy pool stores each position as one row of ``heads * hd``
    lanes: after ``prefill_paged`` and one ``decode_paged``, the row at
    (physical page, in-page slot) of a prompt position and of the decoded
    position equals that layer's K and V projections there, flattened."""
    d, h, hd, ff, page = 32, 4, 8, 64, 8
    plan = ExecPlan.even(1, num_heads=h, d_ff=ff, head_dim=hd, d_model=d)
    layers = hmp.init_stack_params(jax.random.PRNGKey(0), 2, d, h, ff)
    emb = jax.random.normal(jax.random.PRNGKey(1), (64, d)) * 0.5
    ex = GalaxyHMPExecutor(layers, emb, plan, make_mesh_compat((1,), ("model",)))
    pool = ex.make_pool(num_pages=5, page_size=page)
    assert pool[0]["k"].shape == (5, page, h * hd)

    tokens = np.arange(3, 14)  # a 10-token prompt, then one decoded token
    row = np.asarray([3, 1], np.int32)  # logical page 0 -> 3, 1 -> 1
    _, pool = ex.prefill_paged(jnp.asarray(tokens[None, :10]), pool,
                               jnp.asarray(row), length=10)
    _, pool = ex.decode_paged(jnp.asarray(tokens[None, 10:]), pool,
                              jnp.asarray(row[None]), jnp.asarray([10]))

    x = emb[tokens][None]  # each layer's input over all 11 positions
    for p, cache in zip(layers, pool):
        for pos in (4, 10):
            phys, within = row[pos // page], pos % page
            for name, w in (("k", p["wk"]), ("v", p["wv"])):
                want = np.einsum("d,dhk->hk", x[0, pos], w).reshape(-1)
                np.testing.assert_allclose(
                    np.asarray(cache[name][phys, within]), want,
                    rtol=1e-5, atol=1e-5)
        x = hmp.reference_layer(p, x)
