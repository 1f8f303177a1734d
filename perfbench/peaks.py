"""Published peaks of each chip, keyed by the ``device_kind`` that JAX
reports. A chip that is not in the table is an error, never a default.

TPU v5e (JAX reports "TPU v5 lite"): Google Cloud documentation, "TPU
v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s in bfloat16, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(LookupError):
    pass


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}")
    return PEAKS[device_kind]
