"""The Galaxy paper's Fig. 2 layer, served through ``GalaxyHMPExecutor``.

Post-LN: ``x = LN(x + MHA(x)); x = LN(x + MLP(x))`` with a GELU (tanh form)
MLP, no biases, no positional encoding, a tied embedding and no final norm.

This file holds what the benchmark needs of the family: the weights, made
on the device from the seed in one jitted call and in the layout the
executor takes; the system under test (the executor on the plan's mesh);
a plain float32 reference that imports nothing of the program; and the
analytic operation and byte counts the metrics divide by.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perfbench import seeded

ROW_BUCKET = 128  # the reference reads logits rows in blocks of this many

# --- sizes --------------------------------------------------------------------


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return d, h, d // h, cfg["intermediate_size"], cfg["vocab_size"]


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["dtype"]).itemsize


# --- analytic counts (the algorithm's, not the implementation's) ----------------


def linear_flops_per_token(cfg: dict) -> float:
    """Projections and MLP of every layer for one token."""
    d, _, _, f, _ = dims(cfg)
    return cfg["num_hidden_layers"] * 2.0 * (4 * d * d + 2 * d * f)


def attention_flops(cfg: dict, position: int) -> float:
    """Scores and weighted values of one query at ``position`` (it attends
    to ``position + 1`` keys), over every layer."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * 4.0 * d * (position + 1)


def unembed_flops(cfg: dict) -> float:
    d, _, _, _, v = dims(cfg)
    return 2.0 * d * v


def weight_bytes(cfg: dict) -> float:
    """Every weight a decode step reads once: the layers and the tied
    table (the logits read all of it)."""
    d, _, _, f, v = dims(cfg)
    per_layer = 4 * d * d + 2 * d * f + 4 * d
    return (cfg["num_hidden_layers"] * per_layer + v * d) * _itemsize(cfg)


def kv_bytes_per_position(cfg: dict) -> float:
    return cfg["num_hidden_layers"] * 2 * cfg["hidden_size"] * _itemsize(cfg)


# --- weights ------------------------------------------------------------------


def _layer_specs() -> Dict[str, P]:
    ax = "model"
    return {"wq": P(None, ax, None), "wk": P(None, ax, None),
            "wv": P(None, ax, None), "wo": P(ax, None, None),
            "w1": P(None, ax), "w2": P(ax, None),
            "ln1_s": P(), "ln1_b": P(), "ln2_s": P(), "ln2_b": P()}


def _init(key, cfg: dict):
    """Seeded weights, one normal draw a layer.  Queries and keys are scaled
    up so attention is peaked (a wrong key position shows in the logits),
    the rest keeps unit activations; norm scales and biases are drawn, so a
    skipped norm shows."""
    d, h, hd, f, v = dims(cfg)
    dt = jnp.dtype(cfg["dtype"])
    layer = {"wq": ((d, h, hd), 2.0 / np.sqrt(d), 0.0),
             "wk": ((d, h, hd), 2.0 / np.sqrt(d), 0.0),
             "wv": ((d, h, hd), 1.0 / np.sqrt(d), 0.0),
             "wo": ((h, hd, d), 1.0 / np.sqrt(d), 0.0),
             "w1": ((d, f), 1.0 / np.sqrt(d), 0.0),
             "w2": ((f, d), 1.0 / np.sqrt(f), 0.0),
             "ln1_s": ((d,), 0.1, 1.0), "ln1_b": ((d,), 0.1, 0.0),
             "ln2_s": ((d,), 0.1, 1.0), "ln2_b": ((d,), 0.1, 0.0)}
    layers = [seeded.draw(jax.random.fold_in(key, i), layer, dt)
              for i in range(cfg["num_hidden_layers"])]
    # small against the layers' unit activations: the tied table would
    # otherwise make every position predict its own input token
    embed = seeded.draw(jax.random.fold_in(key, 1 << 20),
                        ((v, d), 0.02, 0.0), dt)
    return layers, embed


def _mesh(cfg: dict, devices) -> Mesh:
    from repro.launch.mesh import make_mesh_compat
    return make_mesh_compat((cfg["chips"],), ("model",),
                            devices=devices[:cfg["chips"]])


def make_weights(cfg: dict, seed: int, devices):
    """(layers, embed) on the device(s), sharded as the executor holds them,
    from one jitted call."""
    mesh = _mesh(cfg, devices)
    specs = _layer_specs()
    out = ([{k: NamedSharding(mesh, specs[k]) for k in specs}]
           * cfg["num_hidden_layers"], NamedSharding(mesh, P()))
    init = jax.jit(lambda kd: _init(jax.random.wrap_key_data(kd), cfg),
                   out_shardings=out)
    return init(seeded.key_data(seed))


def make_system(cfg: dict, weights, devices):
    """The system under test: ``GalaxyHMPExecutor`` with its defaults on the
    configuration's plan."""
    from repro.core.execplan import ExecPlan
    from repro.serving import GalaxyHMPExecutor

    if cfg["plan"] != "even":
        raise ValueError(f"unknown plan {cfg['plan']!r}")
    d, h, hd, f, _ = dims(cfg)
    plan = ExecPlan.even(cfg["chips"], num_heads=h, d_ff=f, head_dim=hd,
                         d_model=d)
    layers, embed = weights
    return GalaxyHMPExecutor(layers, embed, plan, _mesh(cfg, devices))


# --- plain reference ------------------------------------------------------------


def _round(x, precision: str):
    """Operands of a matmul as the reference's precision holds them:
    float32 as they are; float8 (e4m3) scaled per tensor by the power of
    two that brings its largest entry within the format's range."""
    if precision == "float32":
        return x
    if precision == "float8_e4m3fn":
        top = float(jnp.finfo(jnp.float8_e4m3fn).max)
        scale = 2.0 ** jnp.floor(jnp.log2(top / jnp.max(jnp.abs(x))))
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            / scale
    raise ValueError(precision)


def _ln(x, s, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * s + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _ref_layer(p, x, *, eps, precision):
    p = {k: a.astype(jnp.float32) for k, a in p.items()}
    r = lambda a: _round(a, precision)
    s = x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", r(x), r(p["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", r(x), r(p["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", r(x), r(p["wv"]))
    scores = jnp.einsum("bshk,bthk->bhst", r(q), r(k)) / np.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bhst,bthk->bshk", r(probs), r(v))
    g = jnp.einsum("bshk,hkd->bsd", r(attn), r(p["wo"]))
    x = _ln(x + g, p["ln1_s"], p["ln1_b"], eps)
    hid = _gelu(jnp.einsum("bsd,df->bsf", r(x), r(p["w1"])))
    f = jnp.einsum("bsf,fd->bsd", r(hid), r(p["w2"]))
    return _ln(x + f, p["ln2_s"], p["ln2_b"], eps)


@functools.partial(jax.jit, static_argnames=("precision",))
def _ref_logits(x, embed, rows, *, precision):
    r = lambda a: _round(a.astype(jnp.float32), precision)
    return jnp.einsum("nd,vd->nv", r(x[rows]), r(embed))


def reference_logits(cfg: dict, weights, tokens: np.ndarray, rows: np.ndarray,
                     precision: str = "float32", device=None) -> np.ndarray:
    """Logits at ``rows`` ((n, 2) pairs of sequence and position) of a plain
    forward over ``tokens`` ((B, S), right-padded), in float32 with every
    matmul at full precision, or with its operands rounded to
    ``precision``.  One sequence and one layer at a time, on ``device``."""
    layers, embed = weights
    device = device or jax.devices()[0]
    put = lambda a: jax.device_put(a, device)
    e = put(embed)
    out = np.zeros((len(rows), cfg["vocab_size"]), np.float32)
    with jax.default_matmul_precision("highest"):
        xs = {}
        for b in range(tokens.shape[0]):
            if (rows[:, 0] == b).any():
                xs[b] = e[put(jnp.asarray(tokens[b:b + 1]))].astype(jnp.float32)
        for p in layers:
            p = put(p)
            for b in xs:
                xs[b] = _ref_layer(p, xs[b], eps=cfg["layer_norm_eps"],
                                   precision=precision)
            del p
        for b, x in xs.items():
            mine = np.flatnonzero(rows[:, 0] == b)
            at = np.zeros(-(-len(mine) // ROW_BUCKET) * ROW_BUCKET, np.int32)
            at[:len(mine)] = rows[mine, 1]
            out[mine] = np.asarray(_ref_logits(
                x[0], e, put(jnp.asarray(at)), precision=precision))[:len(mine)]
    return out
