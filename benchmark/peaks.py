"""The chips' published peaks, keyed by ``device_kind``. No override:
a device that is not in the table is an error, not a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
#: at 819 GB/s, per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
