"""Plain reference of a dense decoder-only transformer, from its published
description and the configuration file alone: pre-norm blocks of causal
multi-head attention with rotary positions and a SwiGLU MLP, a final norm
and an output head (the embedding, transposed, where the configuration ties
them).  Straightforward ``jax.numpy`` in float32 with every matrix product
at ``Precision.HIGHEST``; no cache, no batching of requests, no kernels.  It
imports nothing of the program.

``quant="fp8"`` gives the control: the same computation with both operands
of every matrix product rounded to float8 e4m3, each under a scale of its
own, as float8 serving recipes take them.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under a per-tensor scale that maps its
    absolute maximum to the format's largest value."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def mm(eq: str, a: jax.Array, b: jax.Array, quant: Optional[str]) -> jax.Array:
    """A matrix product at HIGHEST precision; under ``quant="fp8"`` its
    operands are first rounded to scaled e4m3."""
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def norm(c: Dict, x: jax.Array) -> jax.Array:
    if c["norm"] != "layernorm_nonparametric":
        raise ValueError(f"unknown norm {c['norm']!r}")
    mu = x.mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(((x - mu) ** 2).mean(-1, keepdims=True) + c["norm_eps"])


def rope(c: Dict, x: jax.Array, pos: jax.Array) -> jax.Array:
    """Rotary embedding over the first ``rotary_fraction`` of each head,
    rotating its two halves against each other (GPT-NeoX layout)."""
    hd = x.shape[-1]
    r = int(hd * c["rotary_fraction"])
    half = r // 2
    inv = 1.0 / (c["rope_theta"] ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(c: Dict, p: Dict, h: jax.Array, quant, q_block: int) -> jax.Array:
    """h: (S, d) of one sequence; causal softmax attention over all of it."""
    S = h.shape[0]
    pos = jnp.arange(S)
    q = rope(c, mm("sd,dnh->snh", h, p["wq"], quant), pos) * c["head_dim"] ** -0.5
    k = rope(c, mm("sd,dnh->snh", h, p["wk"], quant), pos)
    v = mm("sd,dnh->snh", h, p["wv"], quant)
    g = c["n_heads"] // c["n_kv_heads"]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)

    def block(args):
        qb, qpos = args
        s = mm("qnh,tnh->nqt", qb, k, quant)
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        return mm("nqt,tnh->qnh", jax.nn.softmax(s, axis=-1), v, quant)

    nb = max(1, S // q_block) if S % q_block == 0 else 1
    qs, ps = q.reshape(nb, S // nb, *q.shape[1:]), pos.reshape(nb, S // nb)
    o = jax.lax.map(block, (qs, ps)).reshape(q.shape)
    return mm("snh,nhd->sd", o, p["wo"], quant)


def mlp(p: Dict, h: jax.Array, quant) -> jax.Array:
    a = jax.nn.silu(mm("sd,df->sf", h, p["w_gate"], quant)) * mm("sd,df->sf", h, p["w_up"], quant)
    return mm("sf,fd->sd", a, p["w_down"], quant)


def hidden(c: Dict, w: Dict, tokens: jax.Array, quant=None, q_block: int = 512) -> jax.Array:
    """Final normed hidden states (S, d) of one sequence of ids (S,)."""
    x = w["embed"]["tok"][tokens].astype(jnp.float32)
    layers = w["groups"]["b0"]

    def layer(x, p):
        x = x + attention(c, p["attn"], norm(c, x), quant, q_block)
        x = x + mlp(p["mlp"], norm(c, x), quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, layers)
    return norm(c, x)


def head(w: Dict) -> jax.Array:
    return w["lm_head"] if "lm_head" in w else w["embed"]["tok"].T


def logits(c: Dict, w: Dict, tokens: jax.Array, quant=None) -> jax.Array:
    """(S, V) float32 logits of one sequence."""
    with jax.default_matmul_precision("highest"):
        return mm("sd,dv->sv", hidden(c, w, tokens, quant), head(w), quant)

