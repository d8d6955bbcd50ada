"""The one table of peaks, keyed by `device_kind` as JAX reports it. A device
that is not here is an error, never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" system architecture page: 197
#: TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB HBM2e at 819 GB/s, 1,600
#: Gbit/s of inter-chip interconnect per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak numbers for device kind {device_kind!r}; add it to "
            "benchmark/peaks.py with its source"
        )
    return PEAKS[device_kind]
