"""Plain reference of the DeepSeek-V3 block stack (an ``MLAMoEConfig``):
the full forward pass of one sequence, in straightforward ``jax.numpy``,
float32, every matmul at ``highest`` precision, with no cache, no batching
and no kernels.  It imports nothing of the serving program
(``core/mla_moe.py``), which the tests hold to it.

As published (DeepSeek-V3 / Moonlight modelling code, ``model_type``
``deepseek_v3``), per layer::

    h = x + Attn(RMSNorm(x));  x = h + FFN(RMSNorm(h))

* Attention, not absorbed: ``q = x Wq`` per head, split into ``q_nope``
  and ``q_pe``; ``[c; k_pe] = x Wkv_a``, ``c = RMSNorm(c)``; keys
  ``[c Wk_b; RoPE(k_pe)]`` (the RoPE key shared by all heads), values
  ``c Wv_b``; causal softmax at scale ``(nope + rope) ** -0.5``; ``Wo``.
* FFN: dense layers a SwiGLU; MoE layers ``sigmoid(x W_router)`` scores,
  the top ``k`` of score + bias chosen, weights the chosen raw scores over
  their sum (+1e-20) times ``routed_scale``; ``sum_i w_i Expert_i(x) +
  Shared(x)``.
* Final RMSNorm, untied head.

Departures, each deliberate:

* RoPE rotates interleaved pairs ``(x[2i], x[2i+1])``.  The published code
  de-interleaves the pairs before rotating halves, which permutes the
  lanes of q and k alike and leaves every score unchanged.
* The expert share: a layer holds the routed experts whose global ids are
  in ``held`` and the sum runs over the chosen experts it holds (all of
  them when it holds the whole layer); the router still scores every
  expert.  A token none of whose chosen experts is held gets the shared
  experts only.
* ``n_group`` / ``topk_group`` are 1 in the configurations served here, so
  the group-limited selection is the plain top-k and is not written out.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def rope(x, pos, theta):
    """Rotate interleaved pairs of the last axis of x (S, ..., r)."""
    r = x.shape[-1]
    inv = (theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
           ).astype(np.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def attention(cfg, p: Dict, x):
    """Causal MLA over the whole sequence x (S, d), keys and values
    expanded from the latent."""
    s = x.shape[0]
    n, r, rank = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    pos = jnp.arange(s)
    q = jnp.einsum("sd,dhk->shk", x, _f32(p["wq"]))
    q = jnp.concatenate([q[..., :n], rope(q[..., n:], pos, cfg.rope_theta)],
                        -1)
    ckv = x @ _f32(p["wkv_a"])
    c = rms_norm(ckv[:, :rank], p["kv_norm"], cfg.eps)
    k_pe = rope(ckv[:, rank:], pos, cfg.rope_theta)
    k_nope = jnp.einsum("sc,chn->shn", c, _f32(p["wk_b"]))
    v = jnp.einsum("sc,chv->shv", c, _f32(p["wv_b"]))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (*k_nope.shape[:2], r))], -1)
    scores = jnp.einsum("shk,thk->hst", q, k) / np.sqrt(n + r)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("hst,thv->shv", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("shv,hvd->sd", o, _f32(p["wo"]))


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def route(cfg, p: Dict, x):
    """Chosen expert ids (S, k) and their weights (S, k)."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]))
    _, ids = jax.lax.top_k(scores + _f32(p["router_bias"]),
                           cfg.experts_per_token)
    w = jnp.take_along_axis(scores, ids, -1)
    return ids, w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale


def moe(cfg, p: Dict, x):
    """The held routed experts' weighted sum plus the shared experts."""
    ids, w = route(cfg, p, x)
    out = swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
    for j in range(p["held"].shape[0]):
        gate = jnp.sum(jnp.where(ids == p["held"][j], w, 0.0), -1,
                       keepdims=True)
        out = out + gate * swiglu(x, p["e_gate"][j], p["e_up"][j],
                                  p["e_down"][j])
    return out


def layer(cfg, p: Dict, x):
    h = x + attention(cfg, p, rms_norm(x, p["attn_norm"], cfg.eps))
    xn = rms_norm(h, p["ffn_norm"], cfg.eps)
    if "w_gate" in p:
        return h + swiglu(xn, p["w_gate"], p["w_up"], p["w_down"])
    return h + moe(cfg, p, xn)


def forward(cfg, layers: Sequence[Dict], vocab: Dict, tokens) -> jax.Array:
    """Logits (S, V) of one sequence of token ids (S,)."""
    with jax.default_matmul_precision("highest"):
        x = _f32(vocab["table"])[jnp.asarray(tokens)]
        for p in layers:
            x = layer(cfg, p, x)
        return rms_norm(x, vocab["norm"], cfg.eps) @ _f32(vocab["head"])
