"""Three-term roofline of a compiled program.

    compute term    = HLO_FLOPs   / (chips * peak_FLOP/s)
    memory term     = HLO_bytes   / (chips * HBM_bw)
    collective term = coll_bytes  / (chips * link_bw)

FLOPs, bytes and collective bytes are meant to come from
``hlo_cost.analyze`` of a compiled program's post-optimization HLO text.
No entry point builds a ``Roofline`` yet: the chip benchmark is to
report each scoring kernel's roofline share from these terms (ROADMAP,
Open items 1.1), and only ``tests/test_roofline.py`` calls it today.

Hardware constants (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


@dataclasses.dataclass
class Roofline:
    flops: float                 # whole-program HLO FLOPs
    hbm_bytes: float             # whole-program bytes accessed
    collective_bytes: float      # summed collective operand bytes
    chips: int
    per_device: bool             # cost_analysis numbers are per device

    @property
    def compute_s(self) -> float:
        div = 1 if self.per_device else self.chips
        return self.flops / div / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        div = 1 if self.per_device else self.chips
        return self.hbm_bytes / div / HBM_BW

    @property
    def collective_s(self) -> float:
        # HLO text is per-partition under SPMD: bytes are per device
        return self.collective_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def total_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> Dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
        }


def model_flops(n_params: int, tokens: int, active_params: int = 0,
                training: bool = True) -> float:
    """MODEL_FLOPS = 6*N*D (training) or 2*N*D (inference); MoE uses
    active params."""
    n = active_params or n_params
    mult = 6 if training else 2
    return mult * n * tokens
