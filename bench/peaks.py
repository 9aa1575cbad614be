"""Published per-chip peaks, keyed by the `device_kind` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s). A kind that is not in the
table is an error: a roofline against the wrong chip's peaks would pass
unnoticed.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)} (bench/peaks.py)"
        ) from None
