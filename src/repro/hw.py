"""Hardware constants for the TPU v5e-class target (single source of truth).

Used by the roofline analysis (benchmarks/roofline.py) and by the device /
interconnect simulators (repro/sim).  These are the target's published
figures, not measurements: the tests run on the CPU, and what the chip does
is measured by runs on a TPU host (``chip_smoke.py``).
"""
from __future__ import annotations

from dataclasses import dataclass

PS_PER_S = 1_000_000_000_000


@dataclass(frozen=True)
class ChipSpec:
    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12      # FLOP/s per chip
    hbm_bw: float = 819e9                # bytes/s per chip
    hbm_bytes: float = 16e9              # HBM capacity per chip
    vmem_bytes: float = 128 * 2**20      # ~128 MiB VMEM
    ici_link_bw: float = 50e9            # bytes/s per ICI link (per direction)
    ici_links_per_chip: int = 4          # 2D torus: +x/-x/+y/-y
    dcn_bw_per_host: float = 25e9        # bytes/s cross-pod per host
    pcie_bw: float = 32e9                # bytes/s host<->chip
    op_overhead_ps: int = 2_000_000      # ~2us fixed launch overhead per fused op

    # convenience: per-picosecond rates
    @property
    def flops_per_ps(self) -> float:
        return self.peak_flops_bf16 / PS_PER_S

    @property
    def hbm_bytes_per_ps(self) -> float:
        return self.hbm_bw / PS_PER_S

    @property
    def ici_bytes_per_ps(self) -> float:
        return self.ici_link_bw / PS_PER_S


V5E = ChipSpec()
