"""Core layers: norms, RoPE, attention (global/local, GQA, qk-norm, KV cache,
int8 cache), MLP (SwiGLU/GeLU).  Pure functions over param pytrees, each
under a ``jax.named_scope`` (``norm``, ``attn`` with ``kv_write`` for the
cache update, ``mlp``) that names its ops in the compiled program.

Attention uses a *block-causal* reference implementation: a python loop over
query blocks, each attending only the statically-known prefix (or local
window) of KV blocks.  This keeps compiled HLO FLOPs equal to the true
causal FLOPs (no masked-half waste) — which matters because the roofline
analysis reads FLOPs from the compiled artifact — and bounds the live score
tensor to (block × block) instead of (seq × seq).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import PSpec

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if scale is not None:
        x = x * (1.0 + scale.astype(jnp.float32))
    return x.astype(dt)


def layernorm_nonparametric(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """OLMo: LayerNorm without scale/bias."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def norm_pspec(cfg: ModelConfig, width: Optional[int] = None) -> Optional[PSpec]:
    if cfg.nonparametric_ln:
        return None
    return PSpec((width or cfg.d_model,), ("embed_nr",), init="zeros")


@jax.named_scope("norm")
def apply_norm(cfg: ModelConfig, x: jax.Array, scale: Optional[jax.Array],
               dtype: Optional[str] = None) -> jax.Array:
    """The block's norm of the residual stream x, in ``dtype`` (default:
    the compute dtype)."""
    x = x.astype(jnp.float32) if dtype == "float32" else x
    eps = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
    y = layernorm_nonparametric(x, **eps) if cfg.nonparametric_ln else rmsnorm(x, scale, **eps)
    return y.astype(dtype or cfg.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, n, head_dim); positions: (S,)"""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[:, None].astype(jnp.float32) * freqs   # (S, hd/2)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention_pspecs(cfg: ModelConfig) -> Params:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p: Params = {
        "wq": PSpec((d, H, hd), ("embed", "heads", None), init="lecun"),
        "wk": PSpec((d, K, hd), ("embed", "kv_heads", None), init="lecun"),
        "wv": PSpec((d, K, hd), ("embed", "kv_heads", None), init="lecun"),
        "wo": PSpec((H, hd, d), ("heads", None, "embed"), init="lecun"),
    }
    if cfg.qk_norm:
        p["q_norm"] = PSpec((hd,), (None,), init="zeros")
        p["k_norm"] = PSpec((hd,), (None,), init="zeros")
    return p


def _online_block_attn(
    q: jax.Array,              # (B, K, g, Bq, hd) f32-scaled queries
    kv_blocks_k: jax.Array,    # (nb, B, K, Bk, hd)
    kv_blocks_v: jax.Array,
    mask_fn,
    unroll: bool = False,
) -> jax.Array:
    """Online-softmax over a static stack of KV blocks via lax.scan
    (or a python loop when ``unroll`` — cost-measurement mode)."""
    B, K, g, Bq, _ = q.shape
    hd = kv_blocks_v.shape[-1]

    def step(carry, kv):
        m, l, acc, idx = carry
        kb, vb = kv
        s = jnp.einsum("bkgqh,bkth->bkgqt", q, kb.astype(q.dtype))
        s = mask_fn(s, idx)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqt,bkth->bkgqh", p, vb.astype(q.dtype)
        )
        return (m_new, l, acc, idx + 1), None

    m0 = jnp.full((B, K, g, Bq), -jnp.inf, dtype=q.dtype)
    l0 = jnp.zeros((B, K, g, Bq), dtype=q.dtype)
    a0 = jnp.zeros((B, K, g, Bq, hd), dtype=q.dtype)
    carry = (m0, l0, a0, 0)
    if unroll:
        for j in range(kv_blocks_k.shape[0]):
            carry, _ = step(carry, (kv_blocks_k[j], kv_blocks_v[j]))
    else:
        carry, _ = jax.lax.scan(step, carry, (kv_blocks_k, kv_blocks_v))
    m, l, acc, _ = carry
    return acc / jnp.maximum(l, 1e-30)[..., None]


def block_causal_attention(
    cfg: ModelConfig,
    q: jax.Array,          # (B, K, g, S, hd) scaled queries
    k: jax.Array,          # (B, K, S, hd)
    v: jax.Array,          # (B, K, S, hv)
    block_q: int,
    local: bool = False,
) -> jax.Array:
    """Causal (or local-window) attention of every query block over the
    statically known prefix of key blocks: (B, K, g, S, hv) in float32."""
    B, K, g, S, hd = q.shape
    Bq = min(block_q, S)
    n_q = max(S // Bq, 1)
    window_blocks = max(1, -(-cfg.window // Bq)) if local else None

    outs = []
    for i in range(n_q):                     # python loop: static trip counts
        qi = q[:, :, :, i * Bq : (i + 1) * Bq, :].astype(jnp.float32)
        lo = 0 if not local else max(0, i - window_blocks)
        hi = i + 1
        kb = k[:, :, lo * Bq : hi * Bq, :]
        vb = v[:, :, lo * Bq : hi * Bq, :]
        nb = hi - lo
        kb = kb.reshape(B, K, nb, Bq, hd).transpose(2, 0, 1, 3, 4)
        vb = vb.reshape(B, K, nb, Bq, v.shape[-1]).transpose(2, 0, 1, 3, 4)

        q_pos = i * Bq + jnp.arange(Bq)

        def mask_fn(s, idx, lo=lo, q_pos=q_pos):
            k_pos = (lo + idx) * Bq + jnp.arange(Bq)
            m = q_pos[:, None] >= k_pos[None, :]
            if local:
                m &= q_pos[:, None] - k_pos[None, :] < cfg.window
            return jnp.where(m[None, None, None], s, -jnp.inf)

        o = _online_block_attn(qi, kb, vb, mask_fn, unroll=cfg.unroll_inner)
        outs.append(o)                                       # (B,K,g,Bq,hv)
    return jnp.concatenate(outs, axis=3)


@jax.named_scope("kv_write")
def prefill_kv_cache(
    cfg: ModelConfig,
    k: jax.Array,                 # (B, K, S, hd) rope'd keys
    v: jax.Array,
    local: bool,
    max_seq: int,
) -> Dict[str, jax.Array]:
    """Build a decode cache from prefill K/V, in the cache's (S, K, B, hd)
    order (ring-buffer along S for local windows)."""
    B, K, S, hd = k.shape
    entry = init_kv_cache(cfg, B, local, max_seq)
    W = entry["k"].shape[0]
    k, v = k.transpose(2, 1, 0, 3), v.transpose(2, 1, 0, 3)   # (S, K, B, hd)
    if local:
        n = min(W, S)
        kw, vw = k[S - n :], v[S - n :]
        slots = (S - n + jnp.arange(n)) % W
        write = lambda c, x: c.at[slots].set(x)
    else:
        kw, vw = k, v
        write = lambda c, x: jax.lax.dynamic_update_slice(c, x, (0, 0, 0, 0))
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quantize_kv(kw)
        vq, vs = _quantize_kv(vw)
        entry["k"] = write(entry["k"], kq)
        entry["v"] = write(entry["v"], vq)
        entry["k_scale"] = write(entry["k_scale"], ks)
        entry["v_scale"] = write(entry["v_scale"], vs)
    else:
        entry["k"] = write(entry["k"], kw.astype(entry["k"].dtype))
        entry["v"] = write(entry["v"], vw.astype(entry["v"].dtype))
    return entry


@jax.named_scope("attn")
def multihead_attention(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                       # (B, S, d)
    positions: jax.Array,               # (S,)
    local: bool,
    block_q: Optional[int] = None,
    cache_max_seq: Optional[int] = None,  # build a decode cache when set
) -> Any:
    """Training/prefill attention (block-causal, exact-FLOPs)."""
    if block_q is None:
        block_q = cfg.attn_block_q
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = H // K
    dt = x.dtype

    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(dt))
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cfg.attention_impl == "pallas":
        from ..kernels import ops as kops

        qh = q.transpose(0, 2, 1, 3)                         # (B, H, S, hd)
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        o = kops.flash_attention(
            qh, kh, vh, causal=True, window=cfg.window if local else None,
            block_q=min(512, S), block_k=min(512, S),
        )
        o = o.transpose(0, 2, 1, 3).astype(dt)               # (B, S, H, hd)
        y = jnp.einsum("bsnh,nhd->bsd", o, p["wo"].astype(dt))
        if cache_max_seq is not None:
            return y, prefill_kv_cache(
                cfg, kh, vh, local, cache_max_seq
            )
        return y

    q = q * (hd ** -0.5)

    # (B, K, g, S, hd) / (B, K, S, hd)
    q = q.reshape(B, S, K, g, hd).transpose(0, 2, 3, 1, 4)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    out = block_causal_attention(cfg, q, k, v, block_q, local)   # (B,K,g,S,hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(dt)
    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))
    if cache_max_seq is not None:
        return y, prefill_kv_cache(cfg, k, v, local, cache_max_seq)
    return y


# -- decode (KV cache) --------------------------------------------------------


def kv_cache_pspec(cfg: ModelConfig, batch: int, local: bool, max_seq: int) -> Dict[str, Any]:
    """Abstract cache entry for one attention layer.

    Keys and values are (S, K, B, hd): time, kv-head, batch, head-dim.  With
    the layer axis in front, this is the order in which the TPU compiler
    keeps the stacked cache that the decode scan carries, writes one token
    into in place and reads each layer from where it lies; the
    (B, K, S, hd) order makes it relayout the whole cache around the loop.
    """
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    S = min(cfg.window, max_seq) if local else max_seq
    cdt = {"int8": jnp.int8, "bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg.kv_cache_dtype
    ]
    entry = {
        "k": jax.ShapeDtypeStruct((S, K, batch, hd), cdt),
        "v": jax.ShapeDtypeStruct((S, K, batch, hd), cdt),
    }
    if cfg.kv_cache_dtype == "int8":
        entry["k_scale"] = jax.ShapeDtypeStruct((S, K, batch, 1), jnp.float32)
        entry["v_scale"] = jax.ShapeDtypeStruct((S, K, batch, 1), jnp.float32)
    return entry


def init_kv_cache(cfg: ModelConfig, batch: int, local: bool, max_seq: int) -> Dict[str, jax.Array]:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), kv_cache_pspec(cfg, batch, local, max_seq)
    )


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


@jax.named_scope("attn")
def decode_attention(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                  # (B, 1, d)
    cache: Dict[str, jax.Array],   # (S, K, B, hd) leaves, or (G, S, K, B, hd) with ``layer``
    pos: jax.Array,                # scalar int32: number of tokens already cached
    local: bool,
    layer: Optional[jax.Array] = None,   # scalar int32: this layer's index in a stacked cache
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One token of attention through the KV cache.  The step's K/V go into
    slot ``pos`` (``pos % S`` for local windows) with one in-place
    ``dynamic_update_slice`` per leaf; the attention then reads this layer's
    keys and values where they lie.  With ``layer`` set the cache is the
    scan's stacked carry and both index it at the layer."""
    B, _, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = H // K
    dt = x.dtype
    S = cache["k"].shape[-4]

    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(dt))
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    pos_arr = pos[None] if pos.ndim == 0 else pos
    q = apply_rope(q, pos_arr, cfg.rope_theta) * (hd ** -0.5)
    k = apply_rope(k, pos_arr, cfg.rope_theta)

    # write position: ring-buffer for local windows, linear otherwise
    slot = pos % S if local else pos
    lead = () if layer is None else (layer,)

    def write(buf, new):           # new: (1, K, B, last)
        new = new.astype(buf.dtype).reshape((1,) * len(lead) + new.shape)
        return jax.lax.dynamic_update_slice(buf, new, (*lead, slot, 0, 0, 0))

    def read(buf):
        return buf if layer is None else jax.lax.dynamic_index_in_dim(buf, layer, 0, False)

    k_new = k.transpose(1, 2, 0, 3)        # (1, K, B, hd)
    v_new = v.transpose(1, 2, 0, 3)
    cache = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        with jax.named_scope("kv_write"):
            kq, ks = _quantize_kv(k_new)
            vq, vs = _quantize_kv(v_new)
            cache["k"] = write(cache["k"], kq)
            cache["v"] = write(cache["v"], vq)
            cache["k_scale"] = write(cache["k_scale"], ks)
            cache["v_scale"] = write(cache["v_scale"], vs)
        # dequantize to bf16 (int8 values are exact in bf16) and keep the
        # attention dots in bf16 with f32 accumulation — avoids an f32
        # materialization of the whole cache.  The Pallas decode kernel
        # (kernels/decode_attention.py) streams int8 directly on TPU.
        keys = read(cache["k"]).astype(jnp.bfloat16) * read(cache["k_scale"]).astype(jnp.bfloat16)
        vals = read(cache["v"]).astype(jnp.bfloat16) * read(cache["v_scale"]).astype(jnp.bfloat16)
    else:
        with jax.named_scope("kv_write"):
            cache["k"] = write(cache["k"], k_new)
            cache["v"] = write(cache["v"], v_new)
        keys = read(cache["k"])
        vals = read(cache["v"])

    # keys/vals: (S, K, B, hd); queries (B, K, g, hd)
    qh = q.reshape(B, K, g, hd).astype(keys.dtype)
    s = jnp.einsum("bkgh,tkbh->bkgt", qh, keys, preferred_element_type=jnp.float32)

    t_idx = jnp.arange(S)
    if local:
        # valid ring-buffer entries: the last min(pos+1, S) written slots
        valid = t_idx < jnp.minimum(pos + 1, S)
    else:
        valid = t_idx <= pos
    s = jnp.where(valid, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bkgt,tkbh->bkgh", w.astype(vals.dtype), vals,
        preferred_element_type=jnp.float32,
    )
    o = o.reshape(B, 1, H, hd).astype(dt)
    return jnp.einsum("bsnh,nhd->bsd", o, p["wo"].astype(dt)), cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_pspecs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {
            "w_gate": PSpec((d, f), ("embed", "mlp"), init="lecun"),
            "w_up": PSpec((d, f), ("embed", "mlp"), init="lecun"),
            "w_down": PSpec((f, d), ("mlp", "embed"), init="lecun"),
        }
    return {
        "w_up": PSpec((d, f), ("embed", "mlp"), init="lecun"),
        "w_down": PSpec((f, d), ("mlp", "embed"), init="lecun"),
    }


@jax.named_scope("mlp")
def mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    if cfg.mlp_act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
    elif cfg.mlp_act == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
    else:
        h = jax.nn.gelu(x @ p["w_up"].astype(dt))
    return h @ p["w_down"].astype(dt)
