"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.  A device that is not in the table is an
error: a share of an unknown peak is no number.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s
per chip.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
