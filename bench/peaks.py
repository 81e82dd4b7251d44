"""Published peaks of each chip the benchmark runs on, by ``device_kind``.

A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_s": 197e12,
        "int8_op_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_s": 819e9,
        "ici_bits_s": 1600e9,
        "source": "Google Cloud documentation, \"TPU v5e\" (system "
                  "architecture: peak compute, HBM and interchip "
                  "interconnect per chip)",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
