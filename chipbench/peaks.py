"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.  A device that is not in the table is an
error: a share of an unknown peak is no number.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s
per chip.
"""
from __future__ import annotations

from typing import Dict, Optional

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


def share(seconds: Optional[float], peak: Dict[str, float], *,
          bytes: Optional[float] = None,
          flops: Optional[float] = None) -> Optional[float]:
    """A kernel's share of its roofline: the least time the chip could
    take, the larger of `bytes` over the HBM bandwidth and `flops` over
    the MXU's bf16 peak (of the terms given), over the `seconds` it
    took; None where it took no time or was not seen.  Count only the
    bytes and FLOPs the work cannot do without, so that the share cannot
    pass 1."""
    if not seconds:
        return None
    terms = [v / peak[k] for v, k in ((bytes, "hbm_bytes_per_s"),
                                      (flops, "bf16_flops_per_s"))
             if v is not None]
    if not terms:
        raise ValueError("a roofline needs bytes, FLOPs or both")
    return max(terms) / seconds
