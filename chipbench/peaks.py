"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default."""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
SOURCE = "Google Cloud documentation, TPU v5e system architecture"


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {', '.join(sorted(PEAKS))})") from None
