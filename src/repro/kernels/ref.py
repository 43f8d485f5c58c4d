"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def flash_attention_ref(
    q: jax.Array,            # (B, H, S, D)
    k: jax.Array,            # (B, K, S, D)
    v: jax.Array,            # (B, K, S, D)
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    B, H, S, D = q.shape
    K = k.shape[1]
    g = H // K
    scale = scale if scale is not None else D ** -0.5
    qh = q.reshape(B, K, g, S, D).astype(jnp.float32) * scale
    s = jnp.einsum("bkgqd,bktd->bkgqt", qh, k.astype(jnp.float32))
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,bktd->bkgqd", p, v.astype(jnp.float32))
    return o.reshape(B, H, S, D).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,            # (B, H, D) single query per sequence
    k: jax.Array,            # (B, K, S, D)
    v: jax.Array,            # (B, K, S, D)
    valid_len: jax.Array,    # scalar or (B,): number of valid cache slots
    scale: Optional[float] = None,
) -> jax.Array:
    B, H, D = q.shape
    K, S = k.shape[1], k.shape[2]
    g = H // K
    scale = scale if scale is not None else D ** -0.5
    qh = q.reshape(B, K, g, D).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bktd->bkgt", qh, k.astype(jnp.float32))
    t = jnp.arange(S)
    vl = jnp.asarray(valid_len)
    valid = t[None, :] < (vl[:, None] if vl.ndim else vl[None, None])
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def mla_decode_ref(
    q_lat: jax.Array,        # (B, H, rank)
    q_pe: jax.Array,         # (B, H, rope)
    c_kv: jax.Array,         # (L, B, S, rank) stacked latent cache
    k_pe: jax.Array,         # (L, B, rope, S) stacked rotary keys
    layer: jax.Array,        # scalar int32
    pos: jax.Array,          # scalar int32: last live slot
    scale: float,
) -> jax.Array:
    c = jax.lax.dynamic_index_in_dim(c_kv, layer, 0, False).astype(jnp.float32)
    r = jax.lax.dynamic_index_in_dim(k_pe, layer, 0, False).astype(jnp.float32)
    s = (jnp.einsum("bhr,btr->bht", q_lat.astype(jnp.float32), c)
         + jnp.einsum("bhe,bet->bht", q_pe.astype(jnp.float32), r)) * scale
    s = jnp.where(jnp.arange(c.shape[1]) <= pos, s, -jnp.inf)
    return jnp.einsum("bht,btr->bhr", jax.nn.softmax(s, axis=-1), c)


def rglru_scan_ref(
    a: jax.Array,            # (B, L, W) decay in (0,1], f32
    x: jax.Array,            # (B, L, W) gated input, f32
    h0: jax.Array,           # (B, W)
) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + x_t.  Returns (h_all (B,L,W), h_final)."""

    def step(h, ax):
        at, xt = ax
        h = at * h + xt
        return h, h

    hT, hs = jax.lax.scan(step, h0, (a.swapaxes(0, 1), x.swapaxes(0, 1)))
    return hs.swapaxes(0, 1), hT


def ssm_scan_ref(
    a: jax.Array,            # (B, L, Di, N) decay
    bx: jax.Array,           # (B, L, Di, N) input
    c: jax.Array,            # (B, L, N) output projection
    h0: jax.Array,           # (B, Di, N)
) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t*h + bx_t; y_t = sum_N h_t * c_t.  Returns (y (B,L,Di), h_T)."""

    def step(h, inp):
        at, bxt, ct = inp
        h = at * h + bxt
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    hT, ys = jax.lax.scan(
        step, h0, (a.swapaxes(0, 1), bx.swapaxes(0, 1), c.swapaxes(0, 1))
    )
    return ys.swapaxes(0, 1), hT


def rmsnorm_ref(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    n = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (n * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
