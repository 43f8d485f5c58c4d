"""Absorbed MLA decode — Pallas TPU kernel for one token of multi-head
latent attention over the latent cache.

Every head's query has been folded into latent space (``q_nope W_uk^T``,
width kv_lora_rank, beside its rotary part ``q_pe``), so all heads of a row
attend to the same cached ``c_kv`` and ``k_pe``: per batch row, the scores
``q_lat · c_kvᵀ + q_pe · k_pe``, a softmax over the live slots ``t <= pos``,
and one (H × S)·(S × rank) product with ``c_kv`` for the latent output.
The cache is read once, where it lies: the kernel takes a layer of the
stacked caches by a scalar-prefetched index, so the decode scan needs no
slice or relayout of them.  ``c_kv`` is (L, B, S, rank), time-major rows
of 512; ``k_pe`` is (L, B, rope, S), time last, the order in which the
device holds an array whose last axis (rope = 64) is not a multiple of 128.

grid = (B,); per row, the blocks (1, H, rank), (1, H, rope), (1, 1, S,
rank) and (1, 1, rope, S): the whole capacity of one row's cache in VMEM
(S × (rank + rope) × 2 B = 1.3 MB at S 1152).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pos_ref, layer_ref, ql_ref, qr_ref, c_ref, r_ref, o_ref, *, scale: float):
    del layer_ref                                    # used by the index maps only
    c = c_ref[0, 0]                                  # (S, rank)
    s = jax.lax.dot_general(ql_ref[0], c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s + jax.lax.dot_general(qr_ref[0], r_ref[0, 0], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (H, S)
    s = s * scale
    t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t <= pos_ref[0], s, -jnp.inf)
    m = s.max(axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=1, keepdims=True)
    o = jax.lax.dot_general(p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (H, rank)
    o_ref[0] = (o / l).astype(o_ref.dtype)


def mla_decode_fwd(
    q_lat: jax.Array,      # (B, H, rank) queries folded into latent space
    q_pe: jax.Array,       # (B, H, rope) rotary queries
    c_kv: jax.Array,       # (L, B, S, rank) stacked latent cache
    k_pe: jax.Array,       # (L, B, rope, S) stacked rotary keys
    layer: jax.Array,      # scalar int32: the layer of the caches to read
    pos: jax.Array,        # scalar int32: the last live slot
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """(B, H, rank) float32: softmax((q_lat · c_kvᵀ + q_pe · k_pe) · scale,
    slots <= pos) · c_kv, at ``layer``."""
    B, H, rank = q_lat.shape
    rope, S = k_pe.shape[2], k_pe.shape[3]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, rank), lambda b, pos, layer: (b, 0, 0)),
            pl.BlockSpec((1, H, rope), lambda b, pos, layer: (b, 0, 0)),
            pl.BlockSpec((1, 1, S, rank), lambda b, pos, layer: (layer[0], b, 0, 0)),
            pl.BlockSpec((1, 1, rope, S), lambda b, pos, layer: (layer[0], b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, pos, layer: (b, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
      q_lat.astype(c_kv.dtype), q_pe.astype(k_pe.dtype), c_kv, k_pe)
