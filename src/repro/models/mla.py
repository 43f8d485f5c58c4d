"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434, as
DeepSeek-V3 and Moonlight use it, with no query compression).

Each token's keys and values come from one latent ``c_kv`` of width
``kv_lora_rank`` (RMS-normed) and one rotary key part ``k_pe`` of width
``qk_rope_head_dim`` that every head shares; only those two are cached:
(kv_lora_rank + qk_rope_head_dim) values a token and layer, for all heads.

- Prefill expands the latent into per-head keys ``[c_kv W_uk, k_pe]`` and
  values ``c_kv W_uv`` and runs the block-causal attention of
  ``layers.py``.
- Decode is the absorbed step: ``W_uk`` is folded into the query
  (``q_nope W_uk^T``, one latent query a head) and ``W_uv`` is applied after
  the weighted sum over the latent cache, so the step reads the latent
  cache once for all heads and never expands it.  The attention over the
  cache is the Pallas kernel ``kernels/mla_decode.py``, which reads its
  layer of the stacked cache where it lies.

Rotary positions apply to the ``qk_rope_head_dim`` part of each query and
to ``k_pe`` only.  The work runs under the ``attn`` scope, the cache writes
under ``kv_write``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from .config import ModelConfig
from .layers import apply_rope, block_causal_attention, rmsnorm
from .params import PSpec

Params = Dict[str, Any]

KV_NORM_EPS = 1e-6     # the latent's RMSNorm keeps its own default


def mla_pspecs(cfg: ModelConfig) -> Params:
    d, H = cfg.d_model, cfg.n_heads
    r, n, e, v = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": PSpec((d, H, n + e), ("embed", "heads", None), init="lecun"),
        "w_dkv": PSpec((d, r), ("embed", None), init="lecun"),
        "w_kr": PSpec((d, e), ("embed", None), init="lecun"),
        "kv_norm": PSpec((r,), (None,), init="zeros"),
        "w_uk": PSpec((r, H, n), (None, "heads", None), init="lecun"),
        "w_uv": PSpec((r, H, v), (None, "heads", None), init="lecun"),
        "wo": PSpec((H, v, d), ("heads", None, "embed"), init="lecun"),
    }


def mla_cache_pspec(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    """One layer's latent cache: ``c_kv`` (B, S, kv_lora_rank), a row a
    token, and ``k_pe`` (B, qk_rope_head_dim, S), a column a token.  Each is
    in the order in which the device holds it (the TPU keeps an array whose
    last axis is not a multiple of 128, as the rotary width 64, with another
    axis last) and the decode kernel reads it, so the decode scan reads each
    layer of its stacked carry where it lies and writes it in place."""
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.kv_cache_dtype]
    return {"c_kv": jax.ShapeDtypeStruct((batch, max_seq, cfg.kv_lora_rank), dt),
            "k_pe": jax.ShapeDtypeStruct((batch, cfg.qk_rope_head_dim, max_seq), dt)}


def _project(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array):
    """x (B, S, d) -> q_nope (B,S,H,n), roped q_pe (B,S,H,e), normed c_kv
    (B,S,r) and roped k_pe (B,S,e), all in x's dtype."""
    dt = x.dtype
    n = cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    q_pe = apply_rope(q[..., n:], positions, cfg.rope_theta)
    c = rmsnorm(x @ p["w_dkv"].astype(dt), p["kv_norm"], KV_NORM_EPS)
    k_pe = apply_rope((x @ p["w_kr"].astype(dt))[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q[..., :n], q_pe, c, k_pe


def _scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


@jax.named_scope("attn")
def mla_attention(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                         # (B, S, d)
    positions: jax.Array,                 # (S,)
    cache_max_seq: Optional[int] = None,  # build the latent cache when set
) -> Any:
    """Training/prefill MLA: the latent expanded into per-head keys and
    values, block-causal attention over them."""
    B, S, _ = x.shape
    H, e = cfg.n_heads, cfg.qk_rope_head_dim
    dt = x.dtype
    q_nope, q_pe, c, k_pe = _project(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rnh->bsnh", c, p["w_uk"].astype(dt))
    v = jnp.einsum("bsr,rnh->bnsh", c, p["w_uv"].astype(dt))                 # (B,H,S,v)
    q = jnp.concatenate([q_nope, q_pe], axis=-1) * _scale(cfg)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H, e))], axis=-1)
    q = q.transpose(0, 2, 1, 3)[:, :, None]                                   # (B,H,1,S,n+e)
    o = block_causal_attention(cfg, q, k.transpose(0, 2, 1, 3), v, cfg.attn_block_q)
    y = jnp.einsum("bnsh,nhd->bsd", o[:, :, 0].astype(dt), p["wo"].astype(dt))
    if cache_max_seq is None:
        return y
    with jax.named_scope("kv_write"):
        spec = mla_cache_pspec(cfg, B, cache_max_seq)
        cache = {name: jax.lax.dynamic_update_slice(jnp.zeros(spec[name].shape, spec[name].dtype),
                                                    new.astype(spec[name].dtype), (0, 0, 0))
                 for name, new in (("c_kv", c), ("k_pe", k_pe.transpose(0, 2, 1)))}
    return y, cache


@jax.named_scope("attn")
def mla_decode(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                   # (B, 1, d)
    cache: Dict[str, jax.Array],    # c_kv (B, S, r), k_pe (B, e, S); stacked (G, ...) with ``layer``
    pos: jax.Array,                 # scalar int32: tokens already cached
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One token of absorbed MLA through the latent cache.  The step's
    ``c_kv`` and ``k_pe`` go into slot ``pos`` with one in-place
    ``dynamic_update_slice`` each (at ``layer`` of a stacked cache)."""
    dt = x.dtype
    q_nope, q_pe, c, k_pe = _project(cfg, p, x, pos[None])
    lead = () if layer is None else (layer,)
    cache = dict(cache)
    with jax.named_scope("kv_write"):
        for name, new, at in (("c_kv", c, (0, pos, 0)), ("k_pe", k_pe.transpose(0, 2, 1), (0, 0, pos))):
            buf = cache[name]
            new = new.astype(buf.dtype).reshape((1,) * len(lead) + new.shape)
            cache[name] = jax.lax.dynamic_update_slice(buf, new, (*lead, *at))
    # the query in latent space: W_uk folded into the non-rotary part
    q_lat = jnp.einsum("bnh,rnh->bnr", q_nope[:, 0], p["w_uk"].astype(dt),
                       preferred_element_type=jnp.float32)
    stacked = [cache[k] if layer is not None else cache[k][None] for k in ("c_kv", "k_pe")]
    o_lat = kops.mla_decode(q_lat, q_pe[:, 0], *stacked, jnp.int32(0) if layer is None else layer,
                            pos, _scale(cfg))                                 # (B, H, r)
    o = jnp.einsum("bnr,rnh->bnh", o_lat.astype(dt), p["w_uv"].astype(dt))
    return jnp.einsum("bnh,nhd->bd", o, p["wo"].astype(dt))[:, None], cache
