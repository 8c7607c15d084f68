"""Published peaks of each chip, keyed by ``device.device_kind``.

A kind that is not in the table is an error, not a default: a share of a
peak against the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # per chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s,
    # 1,600 Gbit/s of inter-chip interconnect.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; ``KeyError`` for a kind not listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
