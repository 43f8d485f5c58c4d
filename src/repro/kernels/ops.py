"""Public jit'd wrappers around the Pallas kernels.

* ``impl="pallas"`` runs the TPU kernel (``interpret=True`` on the CPU
  backend, which executes the kernel body for correctness validation; any
  other non-TPU backend raises).
* ``impl="reference"`` runs the pure-jnp oracle (XLA-native; what the
  dry-runs lower so HLO stays representative).

``flash_attention`` is differentiable under impl="pallas": a custom_vjp
runs the kernel forward and takes the backward through the reference
formula (recompute strategy — the classic flash backward; writing dq/dkv
as Pallas kernels is kernels/flash_attention.py's TODO and does not change
the numerics validated here).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_fwd
from .flash_attention import flash_attention_fwd
from .mla_decode import mla_decode_fwd
from .rglru_scan import rglru_scan_fwd
from .rmsnorm import rmsnorm_fwd
from .ssm_scan import ssm_scan_fwd


def _interpret() -> bool:
    """Compile the kernels for the TPU; interpret them only on the CPU.

    Any other backend raises: a run that meant to use the chip and did not
    find it must not fall back to the interpreter without a word."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for the TPU and interpret on the CPU; "
        f"the default backend is {backend!r}"
    )


# ---------------------------------------------------------------------------
# flash attention (differentiable)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_pallas(q, k, v, causal, window, scale, block_q, block_k):
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )


def _flash_fwd_rule(q, k, v, causal, window, scale, block_q, block_k):
    o = _flash_pallas(q, k, v, causal, window, scale, block_q, block_k)
    return o, (q, k, v)


def _flash_bwd_rule(causal, window, scale, block_q, block_k, res, do):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(q_, k_, v_, causal, window, scale),
        q, k, v,
    )
    return vjp(do)


_flash_pallas.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    impl: str = "pallas",
    block_q: int = 512,
    block_k: int = 512,
) -> jax.Array:
    if impl == "reference":
        return ref.flash_attention_ref(q, k, v, causal, window, scale)
    return _flash_pallas(q, k, v, causal, window, scale, block_q, block_k)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    valid_len: jax.Array,
    scale: Optional[float] = None,
    impl: str = "pallas",
    block_s: int = 512,
) -> jax.Array:
    if impl == "reference":
        return ref.decode_attention_ref(q, k, v, valid_len, scale)
    return decode_attention_fwd(
        q, k, v, valid_len, scale=scale, block_s=block_s, interpret=_interpret()
    )


def mla_decode(
    q_lat: jax.Array,
    q_pe: jax.Array,
    c_kv: jax.Array,
    k_pe: jax.Array,
    layer: jax.Array,
    pos: jax.Array,
    scale: float,
    impl: str = "pallas",
) -> jax.Array:
    if impl == "reference":
        return ref.mla_decode_ref(q_lat, q_pe, c_kv, k_pe, layer, pos, scale)
    return mla_decode_fwd(q_lat, q_pe, c_kv, k_pe, layer, pos, scale, interpret=_interpret())


# ---------------------------------------------------------------------------
# recurrences
# ---------------------------------------------------------------------------


def rglru_scan(
    a: jax.Array, x: jax.Array, h0: jax.Array, impl: str = "pallas",
    block_w: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    if impl == "reference":
        return ref.rglru_scan_ref(a, x, h0)
    return rglru_scan_fwd(a, x, h0, block_w=block_w, interpret=_interpret())


def ssm_scan(
    a: jax.Array, bx: jax.Array, c: jax.Array, h0: jax.Array,
    impl: str = "pallas", block_d: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    if impl == "reference":
        return ref.ssm_scan_ref(a, bx, c, h0)
    return ssm_scan_fwd(a, bx, c, h0, block_d=block_d, interpret=_interpret())


def rmsnorm(
    x: jax.Array, scale: jax.Array, eps: float = 1e-6, impl: str = "pallas",
    block_r: int = 256,
) -> jax.Array:
    if impl == "reference":
        return ref.rmsnorm_ref(x, scale, eps)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = rmsnorm_fwd(x2, scale, eps=eps, block_r=min(block_r, x2.shape[0]),
                      interpret=_interpret())
    return out.reshape(shape)
