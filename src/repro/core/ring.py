"""Ring schedules: tile-granular compute/communication overlap (paper §III-D).

The paper decomposes the GEMM adjacent to each collective into row tiles and
pipelines a D-step ring so each hop's transfer overlaps the previous tile's
GEMM.  This module owns that program through one object:

* ``TileSpec``    — one ring tile: which device owns it, how many of its
  rows are real (``valid``), and how many rows each hop actually ships
  (``bucket``).
* ``RingSchedule`` — the full per-step program: the tiles in ring order, the
  SPMD buffer size (``pad_tile``), the transport mode, whether the schedule
  is double-buffered, and the per-tile compute hook (``gemm``).  For step
  ``r``, device ``i`` holds the tile owned by ``schedule.source(i, r)``, in
  buffer slot ``schedule.buffer_slot(r)``, and its outgoing link carries
  ``bucket[source(i, r)]`` rows on the next hop.

Two overlapped primitives, mirroring the paper's Fig. 6 / Fig. 7, plus two
unoverlapped ``sync_*`` references, all take ``schedule=``:

* ``ring_allgather_matmul``     — AllGather ⊗ GEMM1 (entering a TP block)
* ``matmul_ring_reducescatter`` — GEMM2 ⊗ ReduceScatter (exiting a TP block)

Ragged sequence parallelism (uneven per-device tiles) rides the same ring
through *padded* tiles with per-step valid-length masking: every device's
shard is padded to ``pad_tile = max(tiles)`` rows, and at each step the
receiver zeroes the pad rows of the tile it currently holds before the GEMM,
so pad rows contribute exactly zero to every output — including zero-sized
tiles.  On top of that layout the schedule adds two transport upgrades:

* **Bucketed ragged transport** (``transport="bucketed"``): tile row counts
  are rounded up to a small static set of bucket sizes (``BUCKETS_PER_TILE``
  buckets per tile by default), and each hop ships each tile as a stack of
  row *segments* — one partial ``ppermute`` per distinct bucket boundary,
  with only the devices whose held tile reaches that boundary participating.
  Receivers of an omitted segment get exact zeros, which is precisely what
  those pad rows must hold, so the math is unchanged while each hop moves
  ~``bucket`` rows instead of ``max(tiles)`` rows.  The segment membership
  is solved ahead of trace time (it only depends on the static hop index),
  so the wire program is fully static.
* **Double-buffered overlap** (``double_buffer=True``): hop ``r``'s transfer
  is issued *before* step ``r``'s GEMM consumes the buffer it frees, and the
  two are pinned on opposite sides of an ``optimization_barrier`` — transfer
  genuinely hides behind compute instead of relying on XLA's latency-hiding
  scheduler to reorder it.  The dataflow (and hence the floating-point
  summation order) is identical to the single-buffered schedule.

The global padded layout (which padded row holds which real position) is
owned by ``execplan.SeqLayout``; ``ExecPlan.ring_schedule()`` builds the
matching ``RingSchedule`` from a plan's sequence shares, and
``costmodel.t_ring_exchange`` prices exactly the bucketed bytes the schedule
ships (via ``Plan.seq_wire``).

Pluggable per-tile compute (``ExecPlan.compute_backend``): the schedule's
``gemm(tile, w, valid_rows)`` hook replaces the masked einsum.  Without one
the per-step GEMM is the masked dense dot (the "xla" oracle); with one — the
"pallas" path binds ``kernels.ops.gemm`` with this device's valid counts —
the valid-length kernel owns the row masking itself, so pad *blocks* are
never computed at all.

Shape mismatches raise ``ValueError`` at trace time — a Python ``assert``
would vanish under ``-O`` and produce an opaque XLA shape error for jit
users.  All four functions are bitwise-consistent with each other up to
floating-point summation order (the ring fixes a deterministic order, which
bucketing and double buffering both preserve exactly).

The schedule is the only configuration surface: the pre-schedule keywords
(``tile_size=``, ``valid_sizes=``, ``gemm=``) were deprecated shims for one
release and have been removed — build a ``RingSchedule`` (``.dense`` /
``.ragged`` / ``.with_gemm``) instead.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# per-tile GEMM hook: (x_tile (B,S,d), w (d,F), valid_rows scalar | None)
# -> (B,S,F) with pad rows (rows >= valid_rows) exactly zero
TileGemm = Callable[..., jnp.ndarray]

#: supported wire formats for ragged tiles
RING_TRANSPORTS = ("padded", "bucketed")

#: default bucket granularity: tiles round up to pad_tile/4 row multiples,
#: so a hop decomposes into at most 4 segment ppermutes
BUCKETS_PER_TILE = 4


def _perm(axis_size: int, shift: int = 1):
    return [(i, (i + shift) % axis_size) for i in range(axis_size)]


def _hop_permute(seg, axis_name: str, pairs, d: int):
    """Rotate ``seg`` one ring position for the devices named in ``pairs``.

    ``pairs`` must be a subset of the +1 rotation.  Devices not named as a
    destination receive exact zeros (lax.ppermute's partial-permutation
    semantics).  A ring emulated with ``jax.vmap(axis_name=...)`` (the
    property tests) has no mesh axis, and vmap's ppermute insists on a full
    permutation: there the same semantics are encoded as a sender-side gate
    followed by a full rotation.
    """
    if len(pairs) == d or axis_name in jax.sharding.get_abstract_mesh().manual_axes:
        return jax.lax.ppermute(seg, axis_name, pairs)
    ships = np.zeros(d, dtype=bool)
    ships[[src for src, _ in pairs]] = True
    idx = jax.lax.axis_index(axis_name)
    gated = jnp.where(jnp.asarray(ships)[idx], seg, jnp.zeros_like(seg))
    return jax.lax.ppermute(gated, axis_name, _perm(d))


@dataclasses.dataclass(frozen=True)
class TileSpec:
    """One ring tile: its owner, real rows, and on-wire rows.

    ``valid`` rows of the padded tile hold real sequence positions;
    ``bucket`` (``valid <= bucket <= pad_tile``) is how many rows each ring
    hop ships for this tile — ``pad_tile`` under padded transport, the
    bucket-rounded count under bucketed transport.
    """

    owner: int
    valid: int
    bucket: int


@dataclasses.dataclass(frozen=True)
class RingSchedule:
    """The per-step program of a D-device ring (see module docstring).

    ``tiles`` are in ring order (``tiles[i].owner == i``); ``pad_tile`` is
    the common SPMD buffer size every tile is padded to.  ``gemm`` is the
    optional per-tile compute hook threaded to every step.
    """

    tiles: Tuple[TileSpec, ...]
    pad_tile: int
    transport: str = "padded"
    double_buffer: bool = False
    gemm: Optional[TileGemm] = None

    def __post_init__(self):
        object.__setattr__(self, "tiles", tuple(self.tiles))
        if not self.tiles:
            raise ValueError("RingSchedule needs at least one tile")
        if self.pad_tile < 1:
            raise ValueError(f"pad_tile must be >= 1, got {self.pad_tile}")
        if self.transport not in RING_TRANSPORTS:
            raise ValueError(
                f"unknown ring transport {self.transport!r}; "
                f"expected one of {RING_TRANSPORTS}"
            )
        for i, t in enumerate(self.tiles):
            if t.owner != i:
                raise ValueError(
                    f"tiles must be in ring order: tiles[{i}].owner == {t.owner}"
                )
            if not (0 <= t.valid <= t.bucket <= self.pad_tile):
                raise ValueError(
                    f"tile {i}: need 0 <= valid <= bucket <= pad_tile, got "
                    f"valid={t.valid} bucket={t.bucket} pad_tile={self.pad_tile}"
                )

    # --- constructors ---------------------------------------------------------

    @classmethod
    def ragged(cls, tiles: Sequence[int], *, pad_tile: Optional[int] = None,
               transport: str = "padded", bucket_grain: Optional[int] = None,
               double_buffer: bool = False,
               gemm: Optional[TileGemm] = None) -> "RingSchedule":
        """Schedule for per-device ``tiles`` valid row counts, in ring order.

        Under bucketed transport each tile's wire size rounds up to a
        multiple of ``bucket_grain`` (default ``ceil(pad_tile /
        BUCKETS_PER_TILE)``), clipped to ``pad_tile``; zero tiles ship
        nothing.
        """
        valid = [int(t) for t in tiles]
        if pad_tile is None:
            pad_tile = max(valid) if valid else 0
        pad_tile = int(pad_tile)
        if transport == "bucketed":
            grain = int(bucket_grain) if bucket_grain else max(
                1, -(-pad_tile // BUCKETS_PER_TILE))
            buckets = [min(pad_tile, -(-v // grain) * grain) for v in valid]
        else:
            buckets = [pad_tile] * len(valid)
        specs = tuple(
            TileSpec(owner=i, valid=v, bucket=b)
            for i, (v, b) in enumerate(zip(valid, buckets))
        )
        return cls(specs, pad_tile=pad_tile, transport=transport,
                   double_buffer=double_buffer, gemm=gemm)

    @classmethod
    def dense(cls, num_devices: int, tile_size: int, *,
              transport: str = "padded", double_buffer: bool = False,
              gemm: Optional[TileGemm] = None) -> "RingSchedule":
        """Equal fully-valid tiles — the classic even-split ring."""
        return cls.ragged([tile_size] * num_devices, pad_tile=tile_size,
                          transport=transport, double_buffer=double_buffer,
                          gemm=gemm)

    def with_gemm(self, gemm: Optional[TileGemm]) -> "RingSchedule":
        """The same wire program with a different per-tile compute hook."""
        return dataclasses.replace(self, gemm=gemm)

    # --- static geometry ------------------------------------------------------

    @property
    def num_devices(self) -> int:
        return len(self.tiles)

    @property
    def valid_sizes(self) -> np.ndarray:
        return np.asarray([t.valid for t in self.tiles], int)

    @property
    def buckets(self) -> np.ndarray:
        return np.asarray([t.bucket for t in self.tiles], int)

    @property
    def is_masked(self) -> bool:
        """Whether any tile carries pad rows (per-step masking needed)."""
        return bool((self.valid_sizes < self.pad_tile).any())

    @property
    def is_bucketed(self) -> bool:
        """Whether any hop ships fewer than ``pad_tile`` rows."""
        return self.transport == "bucketed" and bool(
            (self.buckets < self.pad_tile).any())

    @property
    def segment_bounds(self) -> Tuple[int, ...]:
        """Row boundaries of the per-hop wire segments: (0, b_1, .., b_max)."""
        return (0, *sorted({t.bucket for t in self.tiles if t.bucket > 0}))

    def source(self, device, step: int):
        """Owner of the tile ``device`` holds at ring step ``step``."""
        return (device - step) % self.num_devices

    def buffer_slot(self, step: int) -> int:
        """Which of the two tile buffers step ``step`` computes from."""
        return step % 2 if self.double_buffer else 0

    # --- wire accounting (what the hops actually ship) ------------------------

    def hop_rows(self, hop: int) -> np.ndarray:
        """Rows device ``i`` ships on hop ``hop`` (it holds tile source(i, hop))."""
        d = self.num_devices
        return np.asarray(
            [self.tiles[(i - hop) % d].bucket for i in range(d)], int)

    def total_wire_rows(self) -> int:
        """Tile rows shipped across one full rotation (d-1 hops, all links)."""
        return (self.num_devices - 1) * int(self.buckets.sum())

    def padded_wire_rows(self) -> int:
        """What one rotation would ship under padded transport."""
        return (self.num_devices - 1) * self.num_devices * self.pad_tile

    def wire_fraction(self) -> float:
        """Shipped rows as a fraction of the padded-transport rotation."""
        padded = self.padded_wire_rows()
        return self.total_wire_rows() / padded if padded else 1.0

    # --- the hop itself -------------------------------------------------------

    def ship(self, tile, axis_name: str, hop: int):
        """One ring hop (device i -> i+1) of the currently-held tiles.

        Under padded transport this is a single full-tile ``ppermute``.
        Under bucketed transport the tile is shipped as row segments between
        consecutive bucket boundaries; each segment's ppermute names only
        the devices whose held tile reaches that boundary, so receivers of
        an omitted segment get exact zeros (their pad rows).
        """
        d = self.num_devices
        if not self.is_bucketed:
            return jax.lax.ppermute(tile, axis_name, _perm(d))
        buckets = self.buckets
        bounds = self.segment_bounds
        parts = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            pairs = [(i, (i + 1) % d) for i in range(d)
                     if buckets[(i - hop) % d] >= hi]
            seg = jax.lax.slice_in_dim(tile, lo, hi, axis=1)
            parts.append(_hop_permute(seg, axis_name, pairs, d))
        if bounds[-1] < self.pad_tile:
            shape = list(tile.shape)
            shape[1] = self.pad_tile - bounds[-1]
            parts.append(jnp.zeros(shape, tile.dtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _pin(*vals):
    """Pin ``vals`` on opposite sides of the scheduler (identity on values)."""
    return jax.lax.optimization_barrier(vals)


def _resolve_allgather(schedule: Optional[RingSchedule], *, d: int,
                       s_loc: int) -> RingSchedule:
    if schedule is None:
        # default: dense even split over the axis, one local tile per device
        return RingSchedule.dense(d, s_loc)
    if schedule.num_devices != d:
        raise ValueError(
            f"schedule covers {schedule.num_devices} devices "
            f"but the ring has {d}"
        )
    if schedule.pad_tile != s_loc:
        raise ValueError(
            f"local sequence tile is {s_loc} rows but the schedule's "
            f"pad_tile={schedule.pad_tile}; the ring AllGather moves "
            "whole local tiles"
        )
    return schedule


def _resolve_scatter(schedule: Optional[RingSchedule], *, d: int,
                     s: int) -> RingSchedule:
    if schedule is None:
        if s % d:
            raise ValueError(
                f"sequence {s} does not divide over a ring of {d} devices; "
                "pass a schedule, or run a ragged layout "
                "(ExecPlan.ring_schedule / RingSchedule.ragged)"
            )
        return RingSchedule.dense(d, s // d)
    if schedule.num_devices != d:
        raise ValueError(
            f"schedule covers {schedule.num_devices} devices "
            f"but the ring has {d}"
        )
    if d * schedule.pad_tile != s:
        raise ValueError(
            f"tile_size={schedule.pad_tile} x {d} devices != sequence "
            f"{s}; the ring ReduceScatter consumes exactly one tile per "
            "device per step"
        )
    return schedule


def ring_allgather_matmul(x_local, w_local, axis_name: str,
                          *, schedule: Optional[RingSchedule] = None):
    """Overlapped computation of ``all_gather(x, seq) @ w_local``.

    x_local: (B, S_loc, d)   — this device's sequence tile (paper's H_i)
    w_local: (d, F_loc)      — this device's column shard (paper's W_i^D)
    schedule: the ring program (``RingSchedule``); defaults to a dense
              even-split schedule over the axis.  ``pad_tile`` must equal
              ``S_loc`` (every device contributes one tile per ring step).
    returns: (B, D*pad_tile, F_loc) — full-sequence activation (padded
             layout when ragged), local columns.

    Step r computes the GEMM for the tile received r hops ago while the next
    tile is in flight; the final step does no communication (paper §III-D-1).
    """
    d = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, _ = x_local.shape
    sched = _resolve_allgather(schedule, d=d, s_loc=s_loc)
    vs = jnp.asarray(sched.valid_sizes) if sched.is_masked else None
    gemm_fn = sched.gemm
    ts = sched.pad_tile
    f_loc = w_local.shape[1]

    out = jnp.zeros((b, d * ts, f_loc), x_local.dtype)
    tile = x_local
    for r in range(d):
        src = sched.source(idx, r)  # owner of the tile we hold at step r
        nxt = None
        if sched.double_buffer and r != d - 1:
            # issue hop r before the GEMM that frees its buffer and pin the
            # two on opposite sides of the scheduler: the next tile is in
            # flight while this tile computes
            nxt = sched.ship(tile, axis_name, r)
            nxt, tile = _pin(nxt, tile)
        if gemm_fn is not None:
            # valid-length kernel: masks pad rows itself and skips pad blocks
            vrows = None if vs is None else vs[src]
            part = gemm_fn(tile, w_local, vrows)
        else:
            if vs is not None:
                row_ok = jnp.arange(ts) < vs[src]
                gemm_in = jnp.where(row_ok[None, :, None], tile, 0)
            else:
                gemm_in = tile
            part = jnp.einsum("bsd,df->bsf", gemm_in, w_local)
        out = jax.lax.dynamic_update_slice(out, part, (0, src * ts, 0))
        if r != d - 1:
            # send current tile forward; receive the next from the ring
            tile = nxt if nxt is not None else sched.ship(tile, axis_name, r)
    return out


def matmul_ring_reducescatter(h_local, w_local, axis_name: str,
                              *, schedule: Optional[RingSchedule] = None):
    """Overlapped computation of ``psum_scatter(h_local @ w_local, seq)``.

    h_local: (B, S, F_loc)   — full sequence, this device's column shard (E_i)
    w_local: (F_loc, d)      — row shard of the second GEMM (W_i^E)
    schedule: the ring program; defaults to a dense even-split schedule.
              ``D * pad_tile`` must equal ``S`` (the ring consumes exactly
              one tile per device per step).
    returns: (B, pad_tile, d) — this device's sequence tile of the summed
             output.

    Schedule (paper §III-D-2): at step r device i GEMMs its tile
    (i - r + D - 1) mod D and adds the partial sum arriving from its
    predecessor, which processed the same tile one step earlier.  After D
    steps device i owns the fully-reduced tile i.
    """
    d = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s, _ = h_local.shape
    sched = _resolve_scatter(schedule, d=d, s=s)
    vs = jnp.asarray(sched.valid_sizes) if sched.is_masked else None
    gemm_fn = sched.gemm
    ts = sched.pad_tile

    acc = None
    for r in range(d):
        t = jnp.mod(idx - r + d - 1, d)  # tile index to process this step
        tile = jax.lax.dynamic_slice(
            h_local, (0, t * ts, 0), (b, ts, h_local.shape[2])
        )
        inc = None
        if acc is not None and sched.double_buffer:
            # the partial accumulator hop (it carries tile t's partial sums
            # from the predecessor) is issued before this step's GEMM
            inc = sched.ship(acc, axis_name, r)
            inc, tile = _pin(inc, tile)
        if gemm_fn is not None:
            part = gemm_fn(tile, w_local, None if vs is None else vs[t])
        else:
            if vs is not None:
                row_ok = jnp.arange(ts) < vs[t]
                tile = jnp.where(row_ok[None, :, None], tile, 0)
            part = jnp.einsum("bsf,fd->bsd", tile, w_local)
        if acc is None:
            acc = part
        else:
            acc = part + (inc if inc is not None
                          else sched.ship(acc, axis_name, r))
    return acc


# --- unoverlapped references (the paper's "sync" baseline schedule) -----------

def _global_valid_mask(vs: np.ndarray, tile_size: int) -> np.ndarray:
    """(D*tile_size,) bool: valid rows of the concatenated padded layout."""
    return np.concatenate([np.arange(tile_size) < v for v in vs])


def sync_allgather_matmul(x_local, w_local, axis_name: str,
                          *, schedule: Optional[RingSchedule] = None):
    """Unoverlapped oracle for ``ring_allgather_matmul`` (same schedule arg).

    Transport mode and double buffering are ring-only concerns and are
    ignored here; only the schedule's valid row counts and gemm hook apply.
    """
    d = jax.lax.axis_size(axis_name)
    sched = _resolve_allgather(schedule, d=d, s_loc=x_local.shape[1])
    vs = sched.valid_sizes if sched.is_masked else None
    xg = jax.lax.all_gather(x_local, axis_name, axis=1, tiled=True)
    if vs is not None:
        # the gathered sequence mixes per-tile valid counts, which the
        # prefix-valid kernel cannot express: mask rows here either way
        # (a shedding gemm still skips pad column/contraction blocks)
        mask = _global_valid_mask(vs, sched.pad_tile)
        xg = jnp.where(jnp.asarray(mask)[None, :, None], xg, 0)
    if sched.gemm is not None:
        return sched.gemm(xg, w_local, None)
    return jnp.einsum("bsd,df->bsf", xg, w_local)


def sync_matmul_reducescatter(h_local, w_local, axis_name: str,
                              *, schedule: Optional[RingSchedule] = None):
    """Unoverlapped oracle for ``matmul_ring_reducescatter``."""
    d = jax.lax.axis_size(axis_name)
    sched = _resolve_scatter(schedule, d=d, s=h_local.shape[1])
    vs = sched.valid_sizes if sched.is_masked else None
    if vs is not None:
        mask = _global_valid_mask(vs, sched.pad_tile)
        h_local = jnp.where(jnp.asarray(mask)[None, :, None], h_local, 0)
    if sched.gemm is not None:
        out = sched.gemm(h_local, w_local, None)
    else:
        out = jnp.einsum("bsf,fd->bsd", h_local, w_local)
    return jax.lax.psum_scatter(out, axis_name, scatter_dimension=1, tiled=True)
