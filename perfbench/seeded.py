"""Seeded weights drawn in one pass, so the jitted initializer stays small."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_data(seed: int) -> np.ndarray:
    """Raw threefry key data of any seed below 2**64."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def draw(key, leaves, dtype):
    """``leaves``: a pytree of (shape, scale, offset); each leaf becomes
    ``offset + scale * N(0, 1)`` of its shape, all from one normal draw."""
    specs, tree = jax.tree.flatten(
        leaves, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and isinstance(x[0], tuple))
    sizes = [int(np.prod(s)) for s, _, _ in specs]
    z = jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, at = [], 0
    for (shape, scale, offset), n in zip(specs, sizes):
        out.append((offset + scale * z[at:at + n].reshape(shape)).astype(dtype))
        at += n
    return jax.tree.unflatten(tree, out)
