"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default: a share of an unknown peak means nothing."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}

SOURCE = 'Google Cloud documentation, "TPU v5e" (per chip)'


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str
                     ) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
