"""The DeepSeek-V3 block (latent attention, sigmoid-routed experts with
shared experts), served through ``GalaxyMLAExecutor``, the Galaxy HMP
executor's paged protocol over this block.

Per layer, pre-norm RMSNorm: ``h = x + MLA(norm(x)); x = h + FFN(norm(h))``.
MLA: per-head query ``q_nope | q_pe`` (no query compression); one latent a
position (``kv_lora_rank`` lanes after an RMSNorm) plus a RoPE key shared
by every head; keys ``[c Wk_b; RoPE(k_pe)]``, values ``c Wv_b``; scale
``(nope + rope) ** -0.5``.  FFN: a dense SwiGLU for the first
``first_k_dense_replace`` layers, then ``sigmoid(x W_router)`` scores over
``n_routed_experts_published`` experts, the top ``num_experts_per_tok`` of
score + bias, weights the chosen raw scores normalised and scaled by
``routed_scaling_factor``, plus the shared experts.  Final norm, untied
head.  RoPE rotates interleaved pairs.

This chip holds the configuration's share of a stated deployment: the first
``n_routed_experts`` of each layer's routed experts and the first
``vocab_size`` rows of the vocabulary.  The reference is given the same
share: it sums the chosen experts this chip holds.

This file holds what the benchmark needs of the family: the weights, made
on the device from the seed; the system under test (the executor, wrapped
by :class:`Recorder`); a plain float32 reference that imports nothing of
the program, computed in blocks so a 7168-position sequence fits; and the
analytic operation and byte counts the metrics divide by.

**Routing near-ties.**  The program's matmuls run at the configuration's
default precision, which moves the router's input by about 1e-3 relative,
so where a layer's 6th and 7th keys (score + bias) lie closer than that,
program and reference may choose different experts, and either choice is
right.  ``Recorder`` keeps, on the device, the expert ids every program
call chose; after the window they are matched to the sampled requests by
block row and position.  The reference follows the program's choice where
it is a near-tie of the reference's own keys: the least key the program
chose lies within the configuration's ``check.route_margin`` of the
greatest it left out (so the reference's 6th-to-7th margin is under it
too).  Everywhere else it routes by itself, so a wrong route fails
``logit_err``.  It prints how many near-ties it followed.

The recorder lives in this module (``recorder()``): the harness hands the
reference only the configuration and the weights, and a metric reader only
its ``ctx``, whose ``family`` is this module.
"""
from __future__ import annotations

import functools
import sys
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perfbench import seeded

SEQ_BUCKET = 512    # the reference pads each sequence to a multiple of this
QUERY_BLOCK = 512   # query rows of one reference attention block
AXIS = "model"
ATTENTION = ("attn_norm", "wq", "wkv_a", "kv_norm", "wk_b", "wv_b", "wo")

# --- sizes --------------------------------------------------------------------


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["dtype"]).itemsize


def dims(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                n=cfg["qk_nope_head_dim"], r=cfg["qk_rope_head_dim"],
                vd=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
                ff=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
                fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                e=cfg["n_routed_experts"],
                router=cfg["n_routed_experts_published"],
                k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                dense=cfg["first_k_dense_replace"])


def latent_lanes(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


# --- analytic counts (the algorithm's, not the implementation's) ----------------


def _attention_linear(z: dict) -> int:
    """Multiply-adds of one token's attention projections in one layer:
    query, latent, the absorbed key and value maps, output."""
    return (z["d"] * z["h"] * (z["n"] + z["r"]) + z["d"] * (z["rank"] + z["r"])
            + z["rank"] * z["h"] * (z["n"] + z["vd"]) + z["h"] * z["vd"] * z["d"])


def linear_flops_per_token(cfg: dict) -> float:
    """Every layer's projections for one token, the routed experts at the
    held share's expected assignments (``k * held / router`` a token)."""
    z = dims(cfg)
    moe = (z["d"] * z["router"] + 3 * z["d"] * z["fs"]
           + z["k"] * z["e"] / z["router"] * 3 * z["d"] * z["fe"])
    return 2.0 * (z["layers"] * _attention_linear(z)
                  + z["dense"] * 3 * z["d"] * z["ff"]
                  + (z["layers"] - z["dense"]) * moe)


def mla_core_flops(cfg: dict, positions: float) -> float:
    """Absorbed scores (``rank + rope`` lanes) and weighted latents
    (``rank``) of one query over ``positions`` keys, every head and layer."""
    z = dims(cfg)
    return 2.0 * z["layers"] * z["h"] * (2 * z["rank"] + z["r"]) * positions


def attention_flops(cfg: dict, position: int) -> float:
    return mla_core_flops(cfg, position + 1)


def unembed_flops(cfg: dict) -> float:
    z = dims(cfg)
    return 2.0 * z["d"] * z["v"]


def expert_flops(cfg: dict, assignments: float) -> float:
    """Routed experts' operations: 6 · d · moe width per computed
    token-expert pair."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * assignments


def expert_bytes(cfg: dict, active: float) -> float:
    """Weights of ``active`` (expert, layer) pairs that got a token, each
    read once."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * _itemsize(cfg) * active


def kv_bytes_per_position(cfg: dict) -> float:
    """Latent pool bytes of one position over every layer: the latent and
    the RoPE key."""
    return cfg["num_hidden_layers"] * latent_lanes(cfg) * _itemsize(cfg)


def mla_core_bytes(cfg: dict, positions: float) -> float:
    return kv_bytes_per_position(cfg) * positions


def weight_bytes(cfg: dict) -> float:
    """Every weight once: the attention of every layer, the dense SwiGLU,
    each MoE layer's router, shared and held experts, embedding row table
    and head (the logits read all of the head)."""
    z = dims(cfg)
    attn = _attention_linear(z) + 2 * z["d"] + z["rank"]
    moe = (z["d"] * z["router"] + z["router"] + 3 * z["d"] * z["fs"]
           + z["e"] * 3 * z["d"] * z["fe"])
    params = (z["layers"] * attn + z["dense"] * 3 * z["d"] * z["ff"]
              + (z["layers"] - z["dense"]) * moe + 2 * z["v"] * z["d"]
              + z["d"])
    return params * _itemsize(cfg)


# --- weights ------------------------------------------------------------------


def _layer_leaves(cfg: dict, dense: bool) -> Dict:
    """(shape, scale, offset) of a layer's weights.  Queries, latent and
    key maps are drawn at the configuration's ``attention_gain`` over
    1/sqrt(fan-in), so attention is peaked (a wrong key position shows in
    the logits; the reason for its value is under ``assumed``); the rest
    keeps unit activations; norm scales are drawn, so a skipped norm
    shows."""
    z = dims(cfg)
    d, h, rank = z["d"], z["h"], z["rank"]
    s = lambda fan: 1.0 / np.sqrt(fan)
    g = cfg["attention_gain"]
    leaves = {"attn_norm": ((d,), 0.1, 1.0),
              "wq": ((d, h, z["n"] + z["r"]), g * s(d), 0.0),
              "wkv_a": ((d, rank + z["r"]), g * s(d), 0.0),
              "kv_norm": ((rank,), 0.1, 1.0),
              "wk_b": ((rank, h, z["n"]), g * s(rank), 0.0),
              "wv_b": ((rank, h, z["vd"]), s(rank), 0.0),
              "wo": ((h, z["vd"], d), s(h * z["vd"]), 0.0),
              "ffn_norm": ((d,), 0.1, 1.0)}
    if dense:
        leaves.update(w_gate=((d, z["ff"]), s(d), 0.0),
                      w_up=((d, z["ff"]), s(d), 0.0),
                      w_down=((z["ff"], d), s(z["ff"]), 0.0))
    else:
        leaves.update(router=((d, z["router"]), s(d), 0.0),
                      router_bias=((z["router"],), 0.1, 0.0),
                      e_gate=((z["e"], d, z["fe"]), s(d), 0.0),
                      e_up=((z["e"], d, z["fe"]), s(d), 0.0),
                      e_down=((z["e"], z["fe"], d), s(z["fe"]), 0.0),
                      s_gate=((d, z["fs"]), s(d), 0.0),
                      s_up=((d, z["fs"]), s(d), 0.0),
                      s_down=((z["fs"], d), s(z["fs"]), 0.0))
    return leaves


def _init(key, cfg: dict):
    z = dims(cfg)
    dt = jnp.dtype(cfg["dtype"])
    layers = []
    for i in range(z["layers"]):
        p = seeded.draw(jax.random.fold_in(key, i),
                        _layer_leaves(cfg, i < z["dense"]), dt)
        if i >= z["dense"]:
            p["router"] = p["router"].astype(jnp.float32)
            p["router_bias"] = p["router_bias"].astype(jnp.float32)
            p["held"] = jnp.arange(z["e"], dtype=jnp.int32)
        layers.append(p)
    vocab = seeded.draw(jax.random.fold_in(key, 1 << 20),
                        {"table": ((z["v"], z["d"]), 1.0, 0.0),
                         "norm": ((z["d"],), 0.1, 1.0),
                         "head": ((z["d"], z["v"]), 1.0 / np.sqrt(z["d"]),
                                  0.0)}, dt)
    return {"layers": layers, "vocab": vocab}


def _mesh(cfg: dict, devices) -> Mesh:
    from repro.launch.mesh import make_mesh_compat
    return make_mesh_compat((cfg["chips"],), (AXIS,),
                            devices=devices[:cfg["chips"]])


def make_weights(cfg: dict, seed: int, devices):
    """``{"layers": [...], "vocab": {...}}`` on the device(s), sharded as the
    executor holds them (``mla_moe.PARAM_SPECS``), from one jitted call."""
    from repro.core.mla_moe import PARAM_SPECS

    mesh = _mesh(cfg, devices)
    shapes = jax.eval_shape(lambda: _init(jax.random.PRNGKey(0), cfg))
    out = {"layers": [{k: NamedSharding(mesh, PARAM_SPECS[k]) for k in p}
                      for p in shapes["layers"]],
           "vocab": jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                 shapes["vocab"])}
    init = jax.jit(lambda kd: _init(jax.random.wrap_key_data(kd), cfg),
                   out_shardings=out)
    return init(seeded.key_data(seed))


def _program_config(cfg: dict):
    from repro.configs.base import MLAMoEConfig

    z = dims(cfg)
    return MLAMoEConfig(
        name=cfg["name"], source=cfg["source"], num_layers=z["layers"],
        first_dense=z["dense"], d_model=z["d"], num_heads=z["h"],
        qk_nope_dim=z["n"], qk_rope_dim=z["r"], v_head_dim=z["vd"],
        kv_lora_rank=z["rank"], d_ff=z["ff"], d_expert=z["fe"],
        num_shared=cfg["n_shared_experts"], num_experts=z["router"],
        experts_per_token=z["k"], experts_held=z["e"],
        routed_scale=cfg["routed_scaling_factor"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        vocab_size=z["v"], max_position=cfg["max_position_embeddings"])


def make_system(cfg: dict, weights, devices):
    """The system under test: ``GalaxyMLAExecutor`` on a one-device plan,
    given the block's layers, wrapped by a fresh :class:`Recorder`."""
    from repro.core import mla_moe
    from repro.core.execplan import ExecPlan
    from repro.serving import GalaxyMLAExecutor

    global _RECORDER
    if cfg["plan"] != "even":
        raise ValueError(f"unknown plan {cfg['plan']!r}")
    z = dims(cfg)
    plan = ExecPlan.even(cfg["chips"], num_heads=z["h"], d_ff=z["ff"],
                         head_dim=z["vd"], d_model=z["d"])
    layers, vocab = mla_moe.from_arrays(_program_config(cfg),
                                        weights["layers"], weights["vocab"])
    ex = GalaxyMLAExecutor(layers, vocab, plan, _mesh(cfg, devices))
    _RECORDER = Recorder(ex)
    return _RECORDER


# --- the recorder -------------------------------------------------------------------


_RECORDER: Optional["Recorder"] = None


def recorder() -> Optional["Recorder"]:
    """The recorder of the last system made in this process."""
    return _RECORDER


class Recorder:
    """Executor wrapper keeping, on the device, each call's inputs that
    place its rows (prompt tokens and block row; block table and
    positions) and the expert ids the program chose (``ex.routing``).
    Nothing is read back while it serves.  Plain prefills only: a chunked
    prefill's rows are not recorded, and the reference routes them by
    itself.  Also keeps the first decode call's arguments (the pool's as
    shapes, as it is donated), so the decode program can be compiled again
    after the window for its instructions' name stacks (``op_paths``)."""

    def __init__(self, ex):
        self.ex = ex
        # (tokens, block_row, length, ids, decode calls before it)
        self.prefills: List[tuple] = []
        self.decodes: List[tuple] = []    # (block_table, positions, ids)
        self.decode_args: Optional[tuple] = None
        self.paths: Optional[Dict[str, str]] = None
        self._host = None

    def __getattr__(self, name):
        return getattr(self.ex, name)

    def prefill_paged(self, tokens, pool, block_row, length):
        out = self.ex.prefill_paged(tokens, pool, block_row, length)
        self.prefills.append((tokens, block_row, int(length),
                              self.ex.routing, len(self.decodes)))
        return out

    def decode_paged(self, tokens, pool, block_table, positions):
        if self.decode_args is None:
            shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=a.sharding)
            self.decode_args = (tokens, jax.tree.map(shape, pool),
                                block_table, positions)
        out = self.ex.decode_paged(tokens, pool, block_table, positions)
        self.decodes.append((block_table, positions, self.ex.routing))
        return out

    def decode_host(self):
        """(first page of each row, positions) of every decode call, numpy
        (calls, slots), read back once."""
        if self._host is None or len(self._host[0]) != len(self.decodes):
            got = jax.device_get([(bt, pos) for bt, pos, _ in self.decodes])
            self._host = (np.stack([np.asarray(bt)[:, 0] for bt, _ in got]),
                          np.stack([np.asarray(pos) for _, pos in got]))
        return self._host

    def program_routes(self, seq: np.ndarray, prompt: int, n: int
                       ) -> Optional[np.ndarray]:
        """The program's expert ids (moe_layers, n, k) at positions
        ``[0, n)`` of the request whose prompt is ``seq[:prompt]``, -1 where
        no recorded call computed the position; None when no prefill had
        that prompt."""
        firsts = [int(np.asarray(r)[0]) for _, r, _, _, _ in self.prefills]
        found = None
        for c, (tokens, _, length, _, _) in enumerate(self.prefills):
            if length == prompt and np.array_equal(
                    np.asarray(tokens)[0, :prompt], seq[:prompt]):
                found = c
        if found is None:
            return None
        ids = np.asarray(self.prefills[found][3])
        out = np.full((ids.shape[0], n, ids.shape[2]), -1, np.int32)
        out[:, :min(prompt, n)] = ids[:, :min(prompt, n)]
        if not self.decodes:
            return out
        # the decode calls after this prefill whose row starts on its first
        # page, until a later prefill takes that page
        first, start = firsts[found], self.prefills[found][4]
        stop = next((self.prefills[c][4] for c in range(found + 1,
                                                         len(firsts))
                     if firsts[c] == first), len(self.decodes))
        pages, positions = self.decode_host()
        hits = [(c, i) for c in range(start, stop)
                for i in np.flatnonzero((pages[c] == first)
                                        & (positions[c] >= prompt)
                                        & (positions[c] < n))]
        got = jax.device_get([self.decodes[c][2] for c, _ in hits])
        for (c, i), routes in zip(hits, got):
            out[:, positions[c, i]] = np.asarray(routes)[:, i]
        return out


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


def _rms(x, s, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _rope(x, pos, theta):
    """Rotate interleaved pairs of the last axis of x (S, ..., r)."""
    r = x.shape[-1]
    inv = (theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
           ).astype(np.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], -1
                     ).reshape(x.shape)


def _swiglu(x, wg, wu, wd, r):
    h = jax.nn.silu(r(x) @ r(wg)) * (r(x) @ r(wu))
    return r(h) @ r(wd)


@functools.partial(jax.jit, static_argnames=("z", "precision"))
def _ref_attention(p, x, *, z, precision):
    """Causal MLA over the sequence x (S, d), keys and values expanded from
    the latent, queries in blocks of ``QUERY_BLOCK``."""
    r = lambda a: _round(a.astype(jnp.float32), precision)
    s = x.shape[0]
    n, rr, rank = z["n"], z["r"], z["rank"]
    pos = jnp.arange(s)
    xn = _rms(x, p["attn_norm"], z["eps"])
    q = jnp.einsum("sd,dhk->shk", r(xn), r(p["wq"]))
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], pos, z["theta"])], -1)
    ckv = r(xn) @ r(p["wkv_a"])
    c = _rms(ckv[:, :rank], p["kv_norm"], z["eps"])
    k_pe = _rope(ckv[:, rank:], pos, z["theta"])
    k = jnp.einsum("sc,chn->shn", r(c), r(p["wk_b"]))
    k = jnp.concatenate([k, jnp.broadcast_to(k_pe[:, None],
                                             (s, k.shape[1], rr))], -1)
    v = jnp.einsum("sc,chv->shv", r(c), r(p["wv_b"]))
    blk = min(QUERY_BLOCK, s)

    def one(args):
        qb, pb = args
        sc = jnp.einsum("shk,thk->hst", r(qb), r(k)) / np.sqrt(n + rr)
        sc = jnp.where(pos[None, None, :] <= pb[None, :, None], sc, -jnp.inf)
        return jnp.einsum("hst,thv->shv", r(jax.nn.softmax(sc, -1)), r(v))

    o = lax.map(one, (q.reshape(s // blk, blk, *q.shape[1:]),
                      pos.reshape(s // blk, blk))).reshape(s, -1, z["vd"])
    return x + jnp.einsum("shv,hvd->sd", r(o), r(p["wo"]))


@functools.partial(jax.jit, static_argnames=("z", "precision"))
def _ref_ffn(p, h, prog, real, *, z, precision):
    """The feed-forward with its residual.  MoE layers route by their own
    keys, except where the program's choice ``prog`` (S, k; -1 unknown) is
    a near-tie of them: its least key lies within ``z["margin"]`` of the
    greatest key it left out (so the 6th-to-7th margin is under it too).
    There they take the program's choice.  Returns (x, near-ties followed
    where the choices differ, margins under the limit), both counted over
    the ``real`` rows (S,)."""
    r = lambda a: _round(a.astype(jnp.float32), precision)
    xn = _rms(h, p["ffn_norm"], z["eps"])
    if "w_gate" in p:
        return (h + _swiglu(xn, p["w_gate"], p["w_up"], p["w_down"], r),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    scores = jax.nn.sigmoid(r(xn) @ r(p["router"]))
    keys = scores + p["router_bias"]
    top, own = lax.top_k(keys, z["k"] + 1)
    own = own[:, :z["k"]]
    near = ((top[:, z["k"] - 1] - top[:, z["k"]]) < z["margin"]) & real
    known = (prog >= 0).all(-1)
    chosen = (jnp.arange(keys.shape[1]) == prog[..., None]).any(1)
    least = jnp.where(chosen, keys, jnp.inf).min(-1)
    left = jnp.where(chosen, -jnp.inf, keys).max(-1)
    follow = known & real & (least >= left - z["margin"])
    same = (jnp.sort(prog, -1) == jnp.sort(own, -1)).all(-1)
    ids = jnp.where(follow[:, None], prog, own)
    w = jnp.take_along_axis(scores, ids, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * z["scale"]

    def expert(acc, e):
        wg, wu, wd, gid = e
        gate = jnp.sum(jnp.where(ids == gid, w, 0.0), -1, keepdims=True)
        return acc + gate * _swiglu(xn, wg, wu, wd, r), None

    routed, _ = lax.scan(expert, jnp.zeros_like(h),
                         (p["e_gate"], p["e_up"], p["e_down"], p["held"]))
    shared = _swiglu(xn, p["s_gate"], p["s_up"], p["s_down"], r)
    return (h + routed + shared, (follow & ~same).sum(), near.sum())


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _ref_logits(x, norm, head, rows, *, eps, precision):
    r = lambda a: _round(a.astype(jnp.float32), precision)
    return r(_rms(x[rows], norm, eps)) @ r(head)


def _static(cfg: dict) -> tuple:
    z = dims(cfg)
    z.update(eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
             scale=cfg["routed_scaling_factor"],
             margin=cfg["check"]["route_margin"])
    return tuple(sorted(z.items()))


class _Z(dict):
    """Hashable static sizes for the jitted reference."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def reference_logits(cfg: dict, weights, tokens: np.ndarray, rows: np.ndarray,
                     precision: str = "float32", device=None) -> np.ndarray:
    """Logits at ``rows`` ((n, 2) pairs of sequence and position) of a plain
    forward over ``tokens`` ((B, S), right-padded), in float32 with every
    matmul at full precision, or with its operands rounded to
    ``precision``.  One sequence and one layer at a time, each sequence cut
    after its last needed position and padded to ``SEQ_BUCKET``, attention
    in blocks of query rows.  Follows the program's routing at near-ties
    (see the module's docstring) and prints how many it followed."""
    rec = recorder()
    if rec is not None and rec.ex is not None:
        load = rec.ex.read_moe_load()
        print(f"moe: assignments per held expert over the run (moe layer x "
              f"expert), {int(load.sum())} in all: {load.tolist()}; "
              f"counters {rec.ex.metrics.snapshot()['counters']}",
              file=sys.stderr, flush=True)
        rec.ex = None  # the program's state is not needed any more
    z = _Z(_static(cfg))
    device = device or jax.devices()[0]
    put = lambda a: jax.device_put(a, device)
    vocab = {k: put(a) for k, a in weights["vocab"].items()}
    moe_at = [i for i, p in enumerate(weights["layers"]) if "router" in p]
    out = np.zeros((len(rows), cfg["vocab_size"]), np.float32)
    followed = nears = unmatched = 0
    with jax.default_matmul_precision("highest"):
        xs, progs, reals = {}, {}, {}
        for b in range(tokens.shape[0]):
            mine = rows[:, 0] == b
            if not mine.any():
                continue
            n = int(rows[mine, 1].max()) + 1
            s = -(-n // SEQ_BUCKET) * SEQ_BUCKET
            seq = np.zeros(s, np.int32)
            seq[:min(s, tokens.shape[1])] = tokens[b, :s]
            prompt = int(rows[mine, 1].min()) + 1
            prog = (None if rec is None
                    else rec.program_routes(tokens[b], prompt, n))
            if prog is None:
                unmatched += 1
                prog = np.full((len(moe_at), n, z["k"]), -1, np.int32)
            full = np.full((len(moe_at), s, z["k"]), -1, np.int32)
            full[:, :n] = prog
            progs[b] = put(jnp.asarray(full))
            reals[b] = put(jnp.arange(s) < n)
            xs[b] = vocab["table"][put(jnp.asarray(seq))].astype(jnp.float32)
        for i, p in enumerate(weights["layers"]):
            p = {k: put(a) for k, a in p.items()}
            j = moe_at.index(i) if i in moe_at else None
            attn = {k: p[k] for k in ATTENTION}
            for b in xs:
                h = _ref_attention(attn, xs[b], z=z, precision=precision)
                prog = (progs[b][j] if j is not None
                        else jnp.zeros((h.shape[0], z["k"]), jnp.int32))
                xs[b], f, near = _ref_ffn(p, h, prog, reals[b], z=z,
                                          precision=precision)
                followed += int(f)
                nears += int(near)
            del p
        for b, x in xs.items():
            mine = np.flatnonzero(rows[:, 0] == b)
            at = np.zeros(-(-len(mine) // SEQ_BUCKET) * SEQ_BUCKET, np.int32)
            at[:len(mine)] = rows[mine, 1]
            out[mine] = np.asarray(_ref_logits(
                x, vocab["norm"], vocab["head"], put(jnp.asarray(at)),
                eps=cfg["rms_norm_eps"], precision=precision))[:len(mine)]
    print(f"check: routing ({precision}): {followed} near-ties followed "
          f"where the program chose other experts, of {nears} (token, layer) "
          f"margins under {cfg['check']['route_margin']}; {unmatched} "
          "sequences with no recorded routing", file=sys.stderr, flush=True)
    return out


# --- what the metric readers read ---------------------------------------------------


def op_paths(program: str) -> Optional[Dict[str, str]]:
    """The name stack (``jax.named_scope`` path) of each instruction of the
    ``decode`` program the recorded executor ran, by instruction name: the
    program compiled again from its first call's arguments (the same
    function, shapes and shardings, so the same instructions; a compile
    cache returns the very executable).  None for another program or
    without a recorded decode."""
    from perfbench import opscopes
    from repro.serving.galaxy import mla_decode_program

    rec = recorder()
    if program != "decode" or rec is None or rec.ex is None \
            or rec.decode_args is None:
        return None
    if rec.paths is None:
        ex = rec.ex
        text = mla_decode_program(ex.mesh).lower(
            ex.layers, ex.embed, *rec.decode_args, ex.moe_load
        ).compile().as_text()
        rec.paths = opscopes.instruction_paths(text)
    return rec.paths


def decode_window(ctx) -> Optional[List[tuple]]:
    """Per decode call whose ``galaxy.decode`` span starts in the traced
    window: (token-expert pairs computed on this chip, (expert, layer) pairs
    of held experts that got a token, live positions attended), from the
    recorded expert ids, block tables and positions; a call is known by
    the ``call`` number of its span.  None without a recorder or a numbered
    span."""
    from perfbench import progspans

    rec = recorder()
    calls = [int(s.stats["call"]) for s in
             progspans.of_run(ctx).named("galaxy.decode", ctx.lo, ctx.hi)
             if "call" in s.stats]
    if rec is None or rec.ex is None or not calls:
        return None
    held = [np.asarray(l.p["held"]) for l in rec.ex.layers if l.is_moe]
    pages, positions = rec.decode_host()
    out = []
    for c, ids in zip(calls, jax.device_get([rec.decodes[c][2]
                                             for c in calls])):
        ids = np.asarray(ids)
        here = active = 0
        for l, h in enumerate(held):
            hit = ids[l][np.isin(ids[l], h)]
            here += hit.size
            active += np.unique(hit).size
        live = pages[c] != 0
        out.append((here, active, int((positions[c][live] + 1).sum())))
    return out
