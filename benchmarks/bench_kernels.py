"""Kernel benchmarks: each Pallas kernel compiled for the TPU at its
realistic shape (``repro.kernels.cases``), checked against its oracle,
plus the HBM-traffic model that predicts the overlap-fused kernels' win.

Rows are named by device kind. There is no CPU path: on any other
backend the rows fail (interpret mode is for the CPU tests only)."""
from __future__ import annotations

from typing import List

import jax

from repro.kernels.cases import check, kernel_cases
from repro.kernels.flash_attn import hbm_bytes_flash, hbm_bytes_unfused
from repro.kernels.fused_mlp import hbm_bytes_fused
from repro.kernels.fused_mlp.ops import hbm_bytes_unfused as \
    mlp_bytes_unfused
from .common import csv_row, timed


def _hbm_saved(case) -> str:
    """Modelled share of HBM bytes the fusion saves at the case's shape."""
    if case.name == "fused_mlp":
        (m, k), f = case.args[0].shape, case.args[1].shape[1]
        saved = 1 - hbm_bytes_fused(m, k, f) / mlp_bytes_unfused(m, k, f)
    elif case.name == "flash_attention":
        (bh, sq, hd), skv = case.args[0].shape, case.args[1].shape[1]
        saved = 1 - hbm_bytes_flash(bh, sq, skv, hd) / \
            hbm_bytes_unfused(bh, sq, skv, hd)
    else:
        return ""
    return f";hbm_saved={saved:.2f}"


def kernels() -> List[str]:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"kernel rows need a TPU; found {dev.platform}")
    kind = dev.device_kind.replace(" ", "_")
    rows = []
    for case in kernel_cases():
        inputs, _, err = check(case)      # compiles outside the timing
        us, _ = timed(lambda: case.op(*inputs).block_until_ready(),
                      repeats=20)
        rows.append(csv_row(f"kernel_{case.name}[{kind}]", us,
                            f"rel_err={err:.2e}{_hbm_saved(case)}"))
    return rows
