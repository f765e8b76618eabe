"""RingSchedule API: construction, wire accounting, the schedule-only
primitive signatures, and the decode-attention valid-head gather.

These run on a single device: the ring primitives only need a named axis
(``jax.vmap(axis_name=...)``), and the schedule itself is pure host-side
geometry.  Multi-device execution of the transports is covered by
tests/test_execplan.py; hypothesis sweeps live in tests/test_ring_ragged.py.
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import hmp, ring
from repro.core.execplan import ExecPlan
from repro.core.ring import RingSchedule, TileSpec

D_MODEL, F_LOC = 6, 5


# --- construction & geometry --------------------------------------------------

def test_ragged_buckets_round_to_grain():
    s = RingSchedule.ragged((2, 0, 3, 1), pad_tile=8, transport="bucketed")
    # default grain = ceil(8 / BUCKETS_PER_TILE) = 2
    assert tuple(s.buckets) == (2, 0, 4, 2)
    assert tuple(s.valid_sizes) == (2, 0, 3, 1)
    assert s.is_masked and s.is_bucketed
    assert s.segment_bounds == (0, 2, 4)
    # zero tiles ship nothing; wire accounting matches by hand
    assert s.total_wire_rows() == 3 * (2 + 0 + 4 + 2)
    assert s.padded_wire_rows() == 3 * 4 * 8
    assert s.wire_fraction() == pytest.approx(8 / 32)


def test_padded_transport_ships_full_tiles():
    s = RingSchedule.ragged((2, 0, 3, 1), pad_tile=8)
    assert tuple(s.buckets) == (8, 8, 8, 8)
    assert not s.is_bucketed
    assert s.wire_fraction() == 1.0


def test_dense_schedule():
    s = RingSchedule.dense(4, 8, double_buffer=True)
    assert tuple(s.valid_sizes) == (8, 8, 8, 8)
    assert not s.is_masked and not s.is_bucketed
    assert s.buffer_slot(0) == 0 and s.buffer_slot(3) == 1
    # source walks the ring backwards: at step r device i holds tile (i-r)%d
    assert [s.source(1, r) for r in range(4)] == [1, 0, 3, 2]


def test_schedule_validation():
    with pytest.raises(ValueError, match="transport"):
        RingSchedule.ragged((1, 2), transport="compressed")
    with pytest.raises(ValueError, match="pad_tile"):
        RingSchedule.ragged((5, 2), pad_tile=4)  # valid > pad
    with pytest.raises(ValueError, match="bucket"):
        RingSchedule((TileSpec(0, 2, 1),), pad_tile=4)  # valid > bucket
    with pytest.raises(ValueError, match="owner"):
        RingSchedule((TileSpec(1, 2, 2),), pad_tile=4)  # owner != position


# --- schedule-only signatures -------------------------------------------------

def _vmapped(fn, **kw):
    return jax.vmap(lambda a, b: fn(a, b, "ring", **kw), axis_name="ring")


def test_plain_dense_call_does_not_warn():
    x = jnp.ones((2, 1, 4, D_MODEL))
    w = jnp.ones((2, D_MODEL, F_LOC))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _vmapped(ring.ring_allgather_matmul)(x, w)


def test_legacy_kwargs_removed():
    """The PR-6 shims are gone: the pre-schedule keywords now fail like any
    unknown keyword, and the removed hmp paged names no longer exist."""
    x = jnp.ones((2, 1, 4, D_MODEL))
    w = jnp.ones((2, D_MODEL, F_LOC))
    with pytest.raises(TypeError, match="tile_size"):
        _vmapped(ring.ring_allgather_matmul, tile_size=4)(x, w)
    with pytest.raises(TypeError, match="valid_sizes"):
        _vmapped(ring.sync_allgather_matmul, valid_sizes=(4, 4))(x, w)
    assert not hasattr(hmp, "hmp_prefill_paged")
    assert not hasattr(hmp, "hmp_decode_paged")


# --- decode attention: valid-head page gather ---------------------------------

def test_paged_kv_gather_reads_only_valid_head_slots():
    """The uneven-heads decode gather routes pad head slots to the null
    page: valid head slots must match the full gather bitwise even when the
    *other* pages' pad lanes hold garbage, and pad head slots must read
    page 0 (zeros in a real pool) instead of arbitrary pages.  The pool
    stores each position as one row of ``heads * hd`` lanes."""
    rng = np.random.default_rng(0)
    pages, page, h, hd, s, w = 6, 4, 5, 3, 2, 2
    pool = jnp.asarray(rng.normal(size=(pages, page, h * hd)))  # garbage all over
    block_table = jnp.asarray(rng.integers(1, pages, size=(s, w)), jnp.int32)
    head_ok = jnp.asarray([True, True, True, False, False])

    got = hmp._paged_kv_gather(pool, block_table, head_ok)
    full = np.asarray(pool)[np.asarray(block_table)].reshape(s, w * page, h, hd)
    assert got.shape == full.shape
    assert np.array_equal(np.asarray(got)[:, :, :3], full[:, :, :3])
    # pad slots come from the null page, laid out page-major like `full`
    null = np.asarray(pool)[np.zeros((s, w), int)].reshape(s, w * page, h, hd)
    assert np.array_equal(np.asarray(got)[:, :, 3:], null[:, :, 3:])
    # and with an all-valid mask the gather IS the full gather
    all_ok = jnp.ones((h,), bool)
    assert np.array_equal(np.asarray(hmp._paged_kv_gather(pool, block_table,
                                                          all_ok)), full)


# --- ExecPlan threading -------------------------------------------------------

def test_execplan_transport_knobs():
    ep = ExecPlan(heads=(6, 4, 4, 2), columns=(24, 16, 16, 8), head_dim=2,
                  d_model=32, seq_shares=(1.0, 2.0, 2.0, 5.0))
    with pytest.raises(ValueError, match="transport"):
        ep.with_transport("compressed")
    db = ep.with_transport("bucketed", double_buffer=True)
    assert (db.transport, db.double_buffer) == ("bucketed", True)
    assert (ep.transport, ep.double_buffer) == ("padded", False)  # unchanged
    assert "transport=bucketed+db" in db.describe()
    assert "wire=" in db.describe()
    assert "transport=padded" in ep.describe()

    # padded transport ships the straggler's fraction on every hop;
    # bucketed rounds each share up to the top/BUCKETS_PER_TILE grain
    top = 0.5
    assert np.allclose(ep.wire_fractions(), top)
    wf = db.wire_fractions()
    assert np.all(wf <= top + 1e-12)
    assert np.all(wf >= ep.seq_fractions - 1e-12)
    assert wf[0] == pytest.approx(top / ring.BUCKETS_PER_TILE)

    # the simulator's view: seq_wire set only for bucketed transport
    assert ep.to_planner_plan(padded=True).seq_wire is None
    wire = db.to_planner_plan(padded=True).seq_wire
    assert wire is not None and np.allclose(wire, wf)


def test_execplan_ring_schedule_matches_layout():
    ep = ExecPlan(heads=(4, 4, 4, 4), columns=(16, 16, 16, 16), head_dim=2,
                  d_model=32, seq_shares=(1.0, 2.0, 2.0, 5.0),
                  transport="bucketed", double_buffer=True)
    seq = 20
    sched = ep.ring_schedule(seq)
    assert tuple(sched.valid_sizes) == ep.seq_tiles(seq)
    assert sched.pad_tile == ep.seq_tile(seq)
    assert sched.transport == "bucketed" and sched.double_buffer
    gemm = lambda t, s: t
    assert ep.ring_schedule(seq, gemm=gemm).gemm is gemm
