"""Published per-chip peaks, keyed by JAX's ``Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s). Copied from ``repro.roofline.PEAKS`` so that a
change there cannot move the yardstick. A kind that is not here is an error,
never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,  # FLOP/s per chip
        "hbm_bytes_per_s": 819e9,  # B/s per chip
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
