"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (jax locks the device count on first backend init).

Single pod:  16 x 16 = 256 chips, axes (data, model)
Multi-pod:   2 x 16 x 16 = 512 chips, axes (pod, data, model)

The "model" axis is the Galaxy HMP axis (TP heads/ffn/experts + SP sequence);
"data" carries batch / FSDP weight shards / long-context cache shards; "pod"
is the cross-pod (DCN-class) data axis.

``make_mesh_compat`` builds every mesh in this repo — src, tests,
benchmarks — with Auto axis types: ``jax.make_mesh`` defaults to Explicit
axes, and the model zoo's sharding rules rely on GSPMD propagation.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh_compat(shape: Sequence[int], axes: Sequence[str], *,
                     devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with Auto axis types.

    ``devices`` selects an explicit device subset (e.g. the first 4 of 8
    forced host devices, to run a 4-device plan under an 8-device process).
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_test_mesh(model: int = 2, data: int = 1):
    """Small mesh for CPU multi-device tests (subprocess with forced device
    count)."""
    return make_mesh_compat((data, model), ("data", "model"))
