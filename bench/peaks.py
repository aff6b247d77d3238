"""Published peaks of each chip, keyed by JAX's ``device_kind``.

TPU v5e (``TPU v5 lite``): Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
A device not in the table is an error, never a default.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peak"]

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
