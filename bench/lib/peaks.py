"""Published peaks of one accelerator chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): per chip 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.  A device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
