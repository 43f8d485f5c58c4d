"""Pallas TPU kernels (validated on CPU via interpret=True) + jnp oracles.

kernels:
  flash_attention  — online-softmax attention (causal/local, GQA), fwd
  decode_attention — flash-decode single-token attention over long KV
  mla_decode       — absorbed latent attention of one token over the latent cache
  rglru_scan       — RG-LRU diagonal linear recurrence
  ssm_scan         — Mamba-1 selective scan
  rmsnorm          — fused RMSNorm

Each has a pure-jnp oracle in ref.py; ops.py exposes jit-ready wrappers
with impl="pallas"|"reference" dispatch.
"""
from . import ops, ref
from .ops import decode_attention, flash_attention, mla_decode, rglru_scan, rmsnorm, ssm_scan

__all__ = [
    "decode_attention",
    "flash_attention",
    "mla_decode",
    "ops",
    "ref",
    "rglru_scan",
    "rmsnorm",
    "ssm_scan",
]
