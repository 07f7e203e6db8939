"""Published peaks of each accelerator, keyed by ``jax.Device.device_kind``.

A device that is not in the table is an error, never a default: a share of
a peak taken against the wrong chip's numbers is no measurement.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)} (add a row with its source)")
    return PEAKS[device_kind]
