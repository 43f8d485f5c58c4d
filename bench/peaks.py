"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    flops_bf16: float      # FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peak(197e12, 819e9, 16e9, 'Google Cloud documentation, "TPU v5e"')

PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    """The peak of ``device_kind``; a kind that is not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
