"""What the least-work arithmetic of every architecture shares.

An architecture's own FLOPs and bytes (``forward_flops``, ``decode_least``)
live in its module under ``bench/reference/``, which the configuration
names (``harness.arch``).  Those count 2 FLOPs per multiply-add of the
matrix products the model defines and never count recomputation or
padding: they are the least that the work needs, so a share of a peak
built on them cannot pass 100%.
"""
from __future__ import annotations

# bytes of one element of each stored type
BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def causal_sum(start: int, n: int) -> float:
    """Sum of the contexts of positions start .. start+n-1 (each sees itself
    and everything before it)."""
    return n * start + n * (n + 1) / 2.0
